"""A fleet of node pools, on the CPU (PR 44).

Static against state-dependent hard masks (``Session.compute_static_mask``,
``compute_state_mask``), the scenario prescreen that takes the first and
still declines on the second, the reclaim victim filter of the predicates
plugin, and the deployment ``pools-98k`` at 64 and 256 nodes: the
benchmark's own client (``benchmark/generators/pool_reclaim_gangs.py``,
which is ``reclaim_gangs``' loop) drives ``Scheduler.run_once`` over three
pools of labelled nodes, one of them tainted; a PyTorchJob a cycle with a
required node affinity reclaims its GPUs on the nodes it may use while
others stand idle, and is bound a cycle later; and the plain reference the
chip's ``correct`` uses (``benchmark/reference/pool_eviction.py``, loaded
by path, no import of the program) finds all fourteen numbers 0, where the
controls of ``benchmark/tests/control_pools.py`` each move their own.
"""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kai_scheduler_tpu.actions import solvers
from kai_scheduler_tpu.actions.reclaim import survey_reclaim_victims
from kai_scheduler_tpu.framework.conf import SchedulerConfig
from kai_scheduler_tpu.ops import scenario_batch as sb
from kai_scheduler_tpu.utils.metrics import METRICS, _key
from kai_scheduler_tpu.utils.tracing import TRACER
from tests.fixtures import build_session, run_action

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "pools98k-pytorchjob-256"
KEY = "nvidia.com/gpu.product"
A100, H100, H200 = ("NVIDIA-A100-SXM4-80GB", "NVIDIA-H100-80GB-HBM3",
                    "NVIDIA-H200")
HOPPER = [{"expressions": [{"key": KEY, "operator": "In",
                            "values": [H100, H200]}]}]
FILTERED = _key("reclaim_victims_filtered_total",
                {"reason": "excluded-node"})
STRATEGIES = {
    "binpack": None,
    "spread": SchedulerConfig(gpu_placement_strategy="spread",
                              cpu_placement_strategy="spread")}


# -- a fleet of three pools, as a spec -------------------------------------
def pool_spec(seed: int, nodes: int, gang: int = 24, short: int = 0,
              **claimer) -> dict:
    """Three pools of ``nodes`` nodes in contiguous blocks (3/8 A100, 1/2
    H100, 1/8 H200, the last tainted ``reserved``).  Half of each pool is
    under queue ``b``'s preemptible jobs of four one-GPU pods (gang minimum
    two), pinned to their pool and tolerating its taint, some with a GPU or
    two idle beside them; four A100 nodes stand idle (more GPUs than the
    gang asks, where it may not go); every other node is under a whole-node
    pod.  The claimer (queue ``a``): a master beside ``gang - 1`` workers
    that require Hopper and tolerate ``reserved`` (with ``short``, as many
    pods as GPUs stand idle on Hopper nodes and ``short`` more); ``claimer``
    adds keys to every one of its pods."""
    rng = np.random.default_rng([seed, nodes])
    sizes = (3 * nodes // 8, nodes // 2, nodes // 8)
    products = np.repeat([A100, H100, H200], sizes)
    spec = {"nodes": {}, "jobs": {}, "queues": {
        "a": {"deserved": {"gpu": 4 * nodes}},
        "b": {"deserved": {"gpu": nodes // 2}},
        "c": {"deserved": {"gpu": 4 * nodes}}}}
    idle = set(rng.permutation(sizes[0])[:4].tolist())
    idle_on_hopper = 0
    for i, product in enumerate(products.tolist()):
        name = f"n{i:04d}"
        spec["nodes"][name] = {
            "gpu": 8, "cpu": "64", "mem": "512Gi", "labels": {KEY: product},
            "taints": ["reserved"] if product == H200 else []}
        if i in idle:
            continue
        if rng.random() < 0.5:
            pinned = {"selector": {KEY: product}, "tolerations":
                      ["reserved"] if product == H200 else []}
            # Two jobs a node, or one and a half beside idle GPUs.
            second = int(rng.choice([4, 3, 2]))
            idle_on_hopper += (4 - second) * (product != A100)
            for j, pods in enumerate((4, second)):
                spec["jobs"][f"occ-{i:04d}-{j}"] = {
                    "queue": "b", "min_available": 2, "tasks": [
                        {"gpu": 1, "cpu": "4", "mem": "32Gi",
                         "status": "RUNNING", "node": name, **pinned}
                        for _ in range(pods)]}
        else:
            spec["jobs"][f"whole-{i:04d}"] = {
                "queue": "c", "preemptible": False, "tasks": [
                    {"gpu": 8, "cpu": "32", "mem": "256Gi",
                     "status": "RUNNING", "node": name}]}
    if short:
        gang = idle_on_hopper + short
    pod = {"gpu": 1, "mem": "32Gi", "node_affinity": HOPPER,
           "tolerations": ["reserved"], **claimer}
    spec["jobs"]["claimer"] = {
        "queue": "a", "min_available": gang, "preemptible": False,
        "tasks": [{**pod, "cpu": "8"}] + [{**pod, "cpu": "4"}
                                          for _ in range(gang - 1)]}
    return spec


def claimer_tasks(ssn):
    job = ssn.cluster.podgroups["claimer"]
    return job, job.tasks_to_allocate(
        subgroup_order_fn=ssn.pod_set_order_key,
        task_order_fn=ssn.task_order_key, real_allocation=False)


def spy_on_the_prescreen(monkeypatch) -> list:
    """What each prescreen call was sent and what it answered."""
    sent = []
    run_on_nodes = solvers.propose.run_on_nodes

    def spy(ssn, kernel, operands, **kw):
        verdict = run_on_nodes(ssn, kernel, operands, **kw)
        sent.append(types.SimpleNamespace(
            nodes=tuple(np.array(a) for a in ssn._device_arrays()),
            operands=operands, kw=kw, verdict=np.array(verdict)))
        return verdict

    monkeypatch.setattr(solvers.propose, "run_on_nodes", spy)
    return sent


# -- (a) the masked prescreen against the sequential simulation --------------
SEEDS = (3, 11, 3000000019)
WIDTHS = {64: 12, 192: 24, 512: 32}       # nodes -> victim jobs considered


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("nodes", WIDTHS)
def test_the_masked_verdict_is_the_sequential_simulations(
        monkeypatch, nodes, seed, strategy):
    """``verdict[k]`` of the one masked call equals what the solver's own
    simulation gives with the victims of steps 0..k evicted, for every k,
    in 64 bits (the session's call) and in 32 (the same operands through
    the kernel with x64 off, as the chip runs it).  Victims lie on both
    sides of the mask, in an order the seed draws, and more GPUs stand
    idle on nodes the gang may not use than it asks for."""
    sent = spy_on_the_prescreen(monkeypatch)
    ssn = build_session(pool_spec(seed, nodes, short=12),
                        STRATEGIES[strategy])
    job, tasks = claimer_tasks(ssn)
    gang, t_pad = len(tasks), 1 << (len(tasks) - 1).bit_length()
    survey = [pg for pg in survey_reclaim_victims(ssn)
              if pg.queue_id != job.queue_id]
    rng = np.random.default_rng(seed)
    victims = [survey[i] for i in rng.permutation(len(survey))[:WIDTHS[nodes]]]
    builder = solvers.ScenarioBuilder(job, tasks, victims)
    steps = len(builder._steps)
    with TRACER.span("solve:prescreen", kind="solver") as sp:
        verdict = solvers._prescreen_verdict(ssn, tasks, builder, sp)
    assert verdict is not None and len(verdict) == steps
    (call,) = sent
    mask = call.kw["named"]["task_node_mask"]
    assert mask.dtype == bool and mask.shape == (t_pad, len(call.nodes[0]))
    hopper = np.array([n.labels[KEY] != A100
                       for n in ssn.cluster.nodes.values()])
    assert (mask[:gang, :nodes] == hopper).all() and mask[gang:].all()
    # The sequential simulation, prefix by prefix, as ``_solve`` runs it.
    stmt = ssn.statement()
    want = []
    while builder.has_next():
        scenario = builder.next_scenario()
        for task in solvers._unevicted_tasks(scenario, stmt):
            stmt.evict(task)
        cp = stmt.checkpoint()
        want.append(solvers._simulate_attempt(ssn, stmt, scenario, False,
                                              False))
        stmt.rollback(cp)
    stmt.discard()
    assert verdict.tolist() == want
    # Both answers occur, and the idle A100s alone would have seated it.
    assert not want[0] and want[-1]
    assert sum(n.idle[2] for n in ssn.cluster.nodes.values()
               if n.labels[KEY] == A100) >= 32
    with jax.enable_x64(False):
        narrow = sb.batch_prefix_feasibility(
            *(jnp.asarray(a) for a in call.nodes),
            *(jnp.asarray(a) for a in call.operands),
            task_node_mask=jnp.asarray(mask),
            **{k: v for k, v in call.kw.items()
               if k in ("num_prefixes", "gpu_strategy", "cpu_strategy")})
        assert narrow.dtype == bool
        assert jnp.asarray(call.nodes[1]).dtype == jnp.float32
    assert np.asarray(narrow)[:steps].tolist() == want


def test_a_mask_blind_verdict_would_over_admit(monkeypatch):
    """The same rows without their mask: the idle A100s seat the gang at
    every prefix, which is why a static mask has to go with the call."""
    sent = spy_on_the_prescreen(monkeypatch)
    ssn = build_session(pool_spec(3, 64))
    job, tasks = claimer_tasks(ssn)
    survey = [pg for pg in survey_reclaim_victims(ssn)
              if pg.queue_id != job.queue_id]
    builder = solvers.ScenarioBuilder(job, tasks, survey[:12])
    with TRACER.span("solve:prescreen", kind="solver") as sp:
        verdict = solvers._prescreen_verdict(ssn, tasks, builder, sp)
    (call,) = sent
    blind = sb.batch_prefix_feasibility(
        *map(jnp.asarray, call.nodes), *map(jnp.asarray, call.operands),
        num_prefixes=call.kw["num_prefixes"])
    assert np.asarray(blind).all() and not verdict.all()


def test_an_unmasked_call_names_no_mask(monkeypatch):
    """A call that names ``task_node_mask=None`` is another program to
    ``jit`` than one that leaves it out, which is how the three unmasked
    prescreen cells prime theirs: the warm cycle would compile the
    prescreen again (``benchmark/tests`` caught it)."""
    sent = spy_on_the_prescreen(monkeypatch)
    spec = pool_spec(3, 64, gang=128)   # more than stands idle anywhere
    for node in spec["nodes"].values():
        node["labels"] = {}
    for job in spec["jobs"].values():
        for pod in job["tasks"]:
            pod.pop("node_affinity", None)
            pod.pop("selector", None)
    ssn = build_session(spec)
    span, _trace = prescreen_span(ssn)
    assert span.attrs["mask"] == "none" and span.attrs["form"] == "grouped"
    (call,) = sent
    assert call.kw.get("named") is None


# -- (b) static goes, state-dependent still declines -------------------------
def prescreen_span(ssn) -> object:
    TRACER.begin_cycle(1)
    run_action(ssn, "reclaim")
    trace = TRACER.end_cycle()
    return [s for s in trace.spans if s.name == "solve:prescreen"][0], trace


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_a_static_mask_goes_to_the_prescreen(strategy):
    masked = "scenario_prescreen_masked_total"
    steps = "scenario_prescreen_scan_steps_total"
    before = [METRICS.counters.get(c, 0) for c in (masked, steps)]
    ssn = build_session(pool_spec(11, 64), STRATEGIES[strategy])
    span, trace = prescreen_span(ssn)
    assert "declined" not in span.attrs
    assert span.attrs["mask"] == "static"
    # A master beside its workers under one row: two runs, two steps
    # (``scanned`` and ``t_pad`` steps until PR 45).
    assert span.attrs["form"] == "grouped" and span.attrs["runs"] == 2
    assert span.attrs["strategy"] == strategy
    assert span.attrs["t_pad"] == 32
    assert [METRICS.counters[c] - b for c, b in zip(
        (masked, steps), before)] == [1, 2]
    (job,) = [s for s in trace.spans if s.name == "solve:job"]
    assert job.attrs["solved"] and job.attrs["tried"] == 2
    assert job.attrs["skipped"] == span.attrs["first_feasible"] > 0
    gang = ssn.cluster.podgroups["claimer"]
    assert all(ssn.cluster.nodes[t.node_name].labels[KEY] != A100
               for t in gang.pods.values())


@pytest.mark.parametrize("why, claimer, extra", [
    ("host-port", {"host_ports": [8080]}, {}),
    ("bound-pvc", {"pvcs": ["data"]},
     {"pvcs": {"data": {"bound_node": "n0040"}}}),
    ("inter-pod-term", {"anti_affinity_terms": [
        {"selector": {"app": "db"}, "topology_key": KEY}]}, {}),
], ids=lambda p: p if isinstance(p, str) else "")
def test_a_state_dependent_mask_still_declines(why, claimer, extra):
    """A host port (another pod may hold it until it is evicted), a bound
    PVC and an inter-pod term each depend on what runs where: the
    prescreen is not asked, and says ``hard-mask``; the counter of masked
    calls moves by nothing."""
    spec = pool_spec(11, 64, **claimer)
    spec.update(extra)
    # A pod the term matches, and one that holds the port.
    first = next(j for n, j in spec["jobs"].items() if n.startswith("occ"))
    first["tasks"][0].update(labels={"app": "db"}, host_ports=[8080])
    before = METRICS.counters.get("scenario_prescreen_masked_total", 0)
    ssn = build_session(spec)
    job, tasks = claimer_tasks(ssn)
    assert ssn.compute_state_mask(tasks) is not None
    assert ssn.compute_static_mask(tasks) is not None
    both = ssn.compute_hard_mask(tasks)
    assert (both == (ssn.compute_state_mask(tasks)
                     & ssn.compute_static_mask(tasks))).all()
    span, _trace = prescreen_span(ssn)
    assert span.attrs == {"declined": "hard-mask"}
    assert METRICS.counters.get("scenario_prescreen_masked_total", 0) \
        == before


def test_the_static_part_is_the_node_affinitys_alone():
    ssn = build_session(pool_spec(3, 64))
    _job, tasks = claimer_tasks(ssn)
    assert ssn.compute_state_mask(tasks) is None
    static = ssn.compute_static_mask(tasks)
    assert (static == ssn.compute_hard_mask(tasks)).all()
    hopper = np.array([n.labels[KEY] != A100
                       for n in ssn.cluster.nodes.values()])
    assert (static[:, :64] == hopper).all()
    # A pod that selects on nothing brings no mask at all.
    plain = next(pg for uid, pg in ssn.cluster.podgroups.items()
                 if uid.startswith("whole"))
    pods = list(plain.pods.values())
    assert ssn.compute_static_mask(pods) is None
    assert ssn.compute_hard_mask(pods) is None


# -- (c) victims on nodes the reclaimer cannot use --------------------------
def survey_of(ssn, job):
    return [pg for pg in survey_reclaim_victims(ssn)
            if pg.queue_id != job.queue_id]


def pool_of_job(ssn, pg) -> set:
    return {ssn.cluster.nodes[t.node_name].labels[KEY]
            for t in pg.pods.values()}


def test_the_filter_keeps_victims_on_nodes_the_reclaimer_may_use():
    before = METRICS.counters.get(FILTERED, 0)
    ssn = build_session(pool_spec(3, 64))
    assert METRICS.counters[FILTERED] == before     # registered, at 0
    job, _tasks = claimer_tasks(ssn)
    survey = survey_of(ssn, job)
    kept = ssn.filter_reclaim_victims(job, survey)
    assert kept is not survey
    dropped = [pg for pg in survey if pg not in kept]
    assert dropped and kept and len(dropped) + len(kept) == len(survey)
    assert all(pool_of_job(ssn, pg) == {A100} for pg in dropped)
    assert all(pool_of_job(ssn, pg) <= {H100, H200} for pg in kept)
    assert {H100, H200} <= set().union(*(pool_of_job(ssn, pg)
                                         for pg in kept))
    # In the survey's order.
    assert kept == [pg for pg in survey if pg in kept]
    assert METRICS.counters[FILTERED] - before == len(dropped)


def test_a_job_with_pods_on_both_sides_is_kept():
    spec = pool_spec(3, 64)
    a100 = next(n for n, d in spec["nodes"].items()
                if d["labels"][KEY] == A100)
    h100 = next(n for n, d in spec["nodes"].items()
                if d["labels"][KEY] == H100)
    spec["jobs"]["astride"] = {"queue": "b", "min_available": 1, "tasks": [
        {"gpu": 0, "cpu": "1", "status": "RUNNING", "node": a100},
        {"gpu": 0, "cpu": "1", "status": "RUNNING", "node": h100}]}
    spec["jobs"]["a100-only"] = {"queue": "b", "min_available": 1, "tasks": [
        {"gpu": 0, "cpu": "1", "status": "RUNNING", "node": a100}]}
    ssn = build_session(spec)
    job, _tasks = claimer_tasks(ssn)
    kept = {pg.uid for pg in ssn.filter_reclaim_victims(
        job, survey_of(ssn, job))}
    assert "astride" in kept and "a100-only" not in kept


def predicates_plugin(ssn):
    (plugin,) = [p for p in ssn.plugins if p.name == "predicates"]
    return plugin


def test_a_reclaimer_without_constraints_gets_its_list_back_unwalked():
    """No label, no taint, no selector: the same list object, and nothing
    of it is touched (the three other reclaim and consolidation cells pass
    49,152 PodGroups through this hook every cycle)."""
    spec = pool_spec(3, 64)
    for node in spec["nodes"].values():
        node["taints"] = []
    for pod in spec["jobs"]["claimer"]["tasks"]:
        del pod["node_affinity"], pod["tolerations"]
    ssn = build_session(spec)
    job, tasks = claimer_tasks(ssn)

    class Unwalked(list):
        def __iter__(self):
            raise AssertionError("the filter walked the victims")

    victims = Unwalked(survey_of(ssn, job))
    before = METRICS.counters[FILTERED]
    plugin = predicates_plugin(ssn)
    assert plugin.filter_reclaim(job, victims) is victims
    assert METRICS.counters[FILTERED] == before
    # Nor does the consolidation action's bound walk anything for it.
    assert plugin.relocation_bound(job, tasks) is None
    # One taint anywhere makes every pod's tolerations a constraint.
    tainted = build_session(spec | {"nodes": {
        **spec["nodes"], "n0063": {**spec["nodes"]["n0063"],
                                   "taints": ["reserved"]}}})
    job, _tasks = claimer_tasks(tainted)
    with pytest.raises(AssertionError, match="walked"):
        predicates_plugin(tainted).filter_reclaim(
            job, Unwalked(survey_of(tainted, job)))


@pytest.mark.parametrize("constraint, pools", [
    ({"selector": {KEY: H200}, "tolerations": ["reserved"]}, {H200}),
    ({"selector": {KEY: H200}}, set()),
    ({}, {A100, H100}),
    ({"tolerations": ["reserved"]}, {A100, H100, H200}),
], ids=("selector", "selector-untolerated", "taint-alone", "tolerated"))
def test_selectors_and_taints_are_static_constraints_too(constraint, pools):
    """The reclaimer's ``nodeSelector`` and the fleet's taints decide
    which victims are worth evicting, as its affinity does."""
    spec = pool_spec(11, 64)
    for pod in spec["jobs"]["claimer"]["tasks"]:
        del pod["node_affinity"], pod["tolerations"]
        pod.update(constraint)
    ssn = build_session(spec)
    job, _tasks = claimer_tasks(ssn)
    kept = ssn.filter_reclaim_victims(job, survey_of(ssn, job))
    assert set().union(set(), *(pool_of_job(ssn, pg) for pg in kept)) \
        == pools


def test_the_filter_runs_before_the_solvers_cap():
    """With ``max_victims_considered`` 4 and a survey that lists the A100
    victims first (the newest jobs), an unfiltered solver would consider
    four jobs whose eviction frees nothing the gang can use."""
    spec = pool_spec(3, 64, gang=8)
    for name, job in spec["jobs"].items():
        node = job["tasks"][0].get("node")
        if name.startswith("occ") \
                and spec["nodes"][node]["labels"][KEY] == A100:
            job["creation_ts"] = 10.0
    ssn = build_session(spec, SchedulerConfig(max_victims_considered=4))
    job, _tasks = claimer_tasks(ssn)
    survey = survey_of(ssn, job)
    assert all(pool_of_job(ssn, pg) == {A100} for pg in survey[:4])
    TRACER.begin_cycle(1)
    run_action(ssn, "reclaim")
    trace = TRACER.end_cycle()
    (span,) = [s for s in trace.spans if s.name == "reclaim:job"]
    assert span.attrs["success"] and span.attrs["filtered"] > 0
    assert span.attrs["filtered"] + span.attrs["victims"] == len(survey)
    (solve,) = [s for s in trace.spans if s.name == "solve:job"]
    assert solve.attrs["victims"] == 4 and solve.attrs["solved"]


# -- the consolidation action's bound under static constraints ----------------
def consolidation_spans(ssn) -> list:
    TRACER.begin_cycle(1)
    run_action(ssn, "consolidation")
    return [s for s in TRACER.end_cycle().spans
            if s.kind in ("consolidation", "solver")]


def test_consolidation_is_not_tried_where_no_relocation_can_seat_the_gang():
    """Every victim on a Hopper node is pinned to its pool, so nothing
    that moves frees a GPU there; the 32 GPUs idle on A100 nodes pass the
    fleet-wide bound and seat nothing.  Before PR 44 the action simulated
    ``max_scenarios_per_job`` scenarios for such a gang, every cycle."""
    ssn = build_session(pool_spec(3, 64))
    job, tasks = claimer_tasks(ssn)
    bound = predicates_plugin(ssn).relocation_bound(job, tasks)
    hopper = [n for n in ssn.cluster.nodes.values() if n.labels[KEY] != A100]
    assert bound.tolist() == sum(n.idle + n.releasing
                                 for n in hopper).tolist()
    assert bound[2] < 24 <= ssn.node_idle.sum(axis=0)[2]
    spans = consolidation_spans(ssn)
    assert [s.name for s in spans] == ["consolidation:order",
                                       "consolidation:bound"]
    assert spans[1].attrs == {"admitted": 40, "movable": 0}


def test_a_victim_that_may_leave_the_pool_counts_for_the_bound():
    """Take the selector off the Hopper victims: each of them may run on
    an A100 node, so what they hold counts, the bound passes, and the
    action goes on to its solver."""
    spec = pool_spec(3, 64, gang=8)
    freed = 0
    for name, job in spec["jobs"].items():
        for pod in job["tasks"]:
            if name.startswith("occ") and pod["selector"][KEY] != A100:
                pod["selector"] = {}
                freed += 1
    ssn = build_session(spec)
    job, tasks = claimer_tasks(ssn)
    bound = predicates_plugin(ssn).relocation_bound(job, tasks)
    hopper = [n for n in ssn.cluster.nodes.values() if n.labels[KEY] != A100]
    # The H200 victims tolerate the taint; an untainted A100 takes them.
    assert bound[2] == sum(n.idle[2] for n in hopper) + freed
    spans = consolidation_spans(ssn)
    assert "consolidation:job" in [s.name for s in spans]
    assert spans[1].attrs["movable"] == freed


# -- (d) the deployment through the benchmark's own loop -----------------------
DEPLOY_SEEDS = (3, 11, 3000000019)
CUTS = {
    64: dict(nodes=64, pools=(24, 32, 8), idle=4, whole=4, gang=24,
             victims=32, share=0.5, departments=2, leaves=2),
    256: dict(nodes=256, pools=(96, 128, 32), idle=8, whole=8, gang=32,
              victims=64),
}
COUNTERS = (FILTERED, "scenario_prescreen_masked_total",
            "scenario_prescreen_scan_steps_total",
            "node_affinity_masks_built_total", "arena_full_rebuild_total",
            "device_kernel_calls", "scenarios_skipped_by_prescreen_total")


def small_cell(nodes: int):
    """The cell as ``BENCHMARK.json`` names it (its generator and its
    reference loaded by path, as a chip run loads them) with the fleet,
    the gang and the solver's caps cut to ``nodes``."""
    from benchmark.harness import spec
    cell = spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT)
    assert cell.reference.__file__ == os.path.join(
        BENCH, "reference", "pool_eviction.py")
    assert cell.generator.__file__ == os.path.join(
        BENCH, "generators", "pool_reclaim_gangs.py")
    return cell.generator.cut_cell(cell, **CUTS[nodes])


@pytest.fixture(scope="module", params=[
    (nodes, seed) for nodes in CUTS for seed in DEPLOY_SEEDS],
    ids=lambda p: f"{p[0]}n-seed{p[1]}")
def driven(request):
    """Five cycles of the deployment through ``Scheduler.run_once``, and
    the last cycle's trace."""
    nodes, seed = request.param
    cell = small_cell(nodes)
    TRACER.reset()
    client = cell.generator.Client(cell, seed, counters=COUNTERS)
    packs = []
    run_once = client.sched.run_once

    def run_and_note():
        ssn = run_once()
        packs.append(dict(ssn.pack_stats))
        return ssn

    client.sched.run_once = run_and_note
    for _ in range(5):
        client.cycle()
    return types.SimpleNamespace(
        cell=cell, client=client, nodes=nodes, packs=packs,
        trace=TRACER.get_trace(),
        verdict=cell.generator.compare(client.records[1:], client.ledger,
                                       cell))


def test_the_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(BENCH, "reference", "pool_eviction.py")
    tree = ast.parse(open(path).read())
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names} | {
        n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert imported == {"__future__", "math", "numpy"}


def test_every_count_is_zero(driven):
    out, cell = driven.verdict, driven.cell
    assert list(out["compared"]) == list(cell.generator.LIMITS)
    assert len(out["compared"]) == 14
    assert out["correct"], out["compared"]
    assert all(v == [0, 0] for v in out["compared"].values())
    gang = CUTS[driven.nodes]["gang"]
    # One reclaim and one bind in every cycle once the first gang waits.
    assert out["failed"] == 0 and out["attempted"] == 3
    assert out["run"]["evictions_per_cycle"] == [gang]
    assert out["run"]["binds_per_cycle"] == [gang]
    assert out["run"]["bind_cycles_after_arrival"] == [1]
    assert out["run"]["placements_checked"] == out["bound_pods"] == 4 * gang
    pools = CUTS[driven.nodes]["pools"]
    assert out["run"]["nodes_the_gang_may_use"] == pools[1] + pools[2]


def test_the_gang_lands_on_hopper_while_a100s_stand_idle(driven):
    """By the client's own ledger: every bound pod on an H100 or H200
    node, every eviction there too, and the idle A100 nodes idle after
    every cycle with more GPUs than the gang asks."""
    client = driven.client
    pool_of, gang = client.pool_of, CUTS[driven.nodes]["gang"]
    bound = evicted = 0
    for rec in client.records:
        for nodes in rec.bound.values():
            assert all(pool_of[n] > 0 for n in nodes.values())
            bound += len(nodes)
        assert all(pool_of[v.node] > 0 for v in rec.evicted)
        evicted += len(rec.evicted)
        assert (rec.used_after[client.idle_nodes] == 0).all()
        # The gang's nominations, read back from the cache: on Hopper.
        assert len(rec.nominated) == len(rec.evicted) == gang
        assert all(pool_of[node] > 0 for _p, node, *_c in rec.nominated)
    assert bound == 4 * gang and evicted == 5 * gang
    assert 8 * len(client.idle_nodes) >= gang
    assert {int(pool_of[v.node]) for rec in client.records
            for v in rec.evicted} == {1, 2}       # both Hopper pools


def test_the_victims_are_pinned_to_their_pool(driven):
    cluster = driven.client.cluster
    seen = set()
    for pg in cluster.podgroups.values():
        for task in pg.pods.values():
            if not pg.uid.startswith("occ"):
                continue
            node = cluster.nodes[task.node_name]
            assert task.node_selector == {KEY: node.labels[KEY]}
            assert task.tolerations == node.taints
            seen.add(node.labels[KEY])
    assert seen == {A100, H100, H200}
    assert {frozenset(n.taints) for n in cluster.nodes.values()} == {
        frozenset(), frozenset({"reserved"})}


# -- (e) spans, attributes and counters ---------------------------------------
def children(trace, span):
    return [s for s in trace.spans if s.parent_id == span.span_id]


def only(spans, name):
    (span,) = [s for s in spans if s.name == name]
    return span


def test_the_span_tree_of_a_masked_reclaim(driven):
    trace, cut = driven.trace, CUTS[driven.nodes]
    gang, pools = cut["gang"], cut["pools"]
    action = only(trace.spans, "action:reclaim")
    job = only(children(trace, action), "reclaim:job")
    assert job.attrs["success"] is True
    # The A100 victims: the occupancy's share of the pool, two jobs a node.
    share = driven.cell.config["occupancy"]["preemptible_nodes_share"]
    assert job.attrs["filtered"] == int(pools[0] * share) * 2
    survey = only(children(trace, job), "reclaim:survey")
    assert survey.attrs["victims"] >= job.attrs["victims"] \
        + job.attrs["filtered"]
    solve = only(children(trace, job), "solve:job")
    steps = gang // 2
    assert (solve.attrs["tried"], solve.attrs["skipped"],
            solve.attrs["solved"]) == (2, steps - 2, True)
    inside = children(trace, solve)
    assert [s.name for s in inside] == [
        "solve:precheck", "solve:scenario", "solve:prescreen",
        "solve:scenario", "statement:commit"]
    prescreen = only(inside, "solve:prescreen")
    assert prescreen.attrs["form"] == "grouped"
    assert prescreen.attrs["runs"] == 2
    assert prescreen.attrs["mask"] == "static"
    assert prescreen.attrs["strategy"] == "binpack"
    assert prescreen.attrs["first_feasible"] == steps - 2
    assert "declined" not in prescreen.attrs
    only(children(trace, prescreen), "dispatch:scenario_prescreen")
    commit = only(inside, "statement:commit")
    assert commit.attrs == {"binds": 0, "evictions": gang}


def test_every_call_of_the_gang_stages_a_dense_mask(driven):
    trace, nodes = driven.trace, driven.nodes
    operands = [s for s in trace.spans if s.name == "propose:operands"]
    gang = CUTS[nodes]["gang"]
    masked = [s for s in operands if s.attrs["t"] >= gang]
    assert masked and all(s.attrs["mask"] == "dense" for s in masked)
    for span in masked:
        assert span.attrs["mask_bytes"] == span.attrs["t_pad"] * nodes
        assert span.attrs["path"] in ("exact", "multi")


def test_the_node_affinity_mask_is_built_once_a_session(driven):
    spans = [s for s in driven.trace.spans
             if s.name == "predicates:node_affinity"]
    (span,) = spans
    pools = CUTS[driven.nodes]["pools"]
    assert span.kind == "plugin"
    assert span.attrs == {"nodes": driven.nodes,
                          "admitted": pools[1] + pools[2]}
    for rec in driven.client.records:
        assert rec.counters["node_affinity_masks_built_total"] == 1


def test_the_counters_of_a_masked_cycle(driven):
    cut = CUTS[driven.nodes]
    share = driven.cell.config["occupancy"]["preemptible_nodes_share"]
    for rec in driven.client.records[1:]:
        assert rec.counters[FILTERED] == int(cut["pools"][0] * share) * 2
        assert rec.counters["scenario_prescreen_masked_total"] == 1
        # The master's run and the workers', whatever the gang's size.
        assert rec.counters["scenario_prescreen_scan_steps_total"] == 2
        assert rec.counters["scenarios_skipped_by_prescreen_total"] \
            == cut["gang"] // 2 - 2


# -- (f) the snapshot: labels, a taint, and pods that tolerate it ---------------
def test_the_arena_packs_in_full_every_cycle_as_measured(driven):
    """What this PR measured and left (ROADMAP A10): the gang's pods carry
    a toleration and arrive and leave, and the refill's pods a selector,
    so ``vocabulary_signature``, which lists such pods by uid, differs
    every cycle and the host arena packs from scratch."""
    assert driven.packs[0]["full_rebuild"] is True
    assert driven.packs[0]["reason"] == "no-previous-pack"
    for stats in driven.packs[1:]:
        assert stats["full_rebuild"] is True
        assert stats["reason"] == "vocab-change"
    for rec in driven.client.records:
        assert rec.counters["arena_full_rebuild_total"] == 1


def test_the_label_and_taint_columns_are_not_empty(driven):
    from kai_scheduler_tpu.api.snapshot import pack
    snap = pack(driven.client.cluster)
    n = driven.nodes
    assert snap.node_labels.shape == (n, 1)
    assert snap.node_taints.shape == (n, 1)
    assert len(np.unique(snap.node_labels)) == 3
    pools = CUTS[n]["pools"]
    assert (snap.node_taints[:, 0] >= 0).sum() == pools[2]


# -- (d) again: the controls in the program's place ---------------------------
@pytest.mark.parametrize("nodes", CUTS)
@pytest.mark.parametrize("kind", ("mask_blind", "victim_blind",
                                  "selector_blind", "sound"))
def test_a_control_in_the_programs_place_moves_its_own_counts(kind, nodes):
    sys.path.insert(0, os.path.join(BENCH, "tests"))
    try:
        from control_pools import MOVES, as_said, run_control
    finally:
        sys.path.pop(0)
    out = run_control(CELL, 7, kind, cut=CUTS[nodes])
    assert out["correct"] == (kind == "sound")
    assert as_said(out), out["compared"]
    for count in MOVES[kind]:
        assert out["compared"][count][0] > 0


def test_the_trial_stops_a_program_whose_prescreen_declines(monkeypatch):
    """``try_masked_reclaim`` on a program that declines the prescreen for
    any hard mask, as the parent of PR 44 did: 16 scenarios simulated, the
    gang never reclaimed for, status 1 before the fleet is built."""
    from benchmark.harness import spec
    cell = spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT)
    assert cell.generator.try_masked_reclaim(cell, 3)[
        "evictions_per_cycle"] == [64]

    def parent(ssn, tasks, builder, sp):
        sp.set(declined="hard-mask")

    monkeypatch.setattr(solvers, "_prescreen_verdict", parent)
    with pytest.raises(SystemExit) as stop:
        cell.generator.try_masked_reclaim(cell, 3)
    assert "cannot run the configuration pools-98k" in str(stop.value)
    assert "gangs_not_bound" in str(stop.value)
