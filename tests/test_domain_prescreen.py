"""A gang with a REQUIRED topology level reclaims on a full fleet (PR 53).

The scenario prescreen's domain form (``ops/scenario_batch.py``
``domain_verdicts``: ``subset_nodes``' own rule, ``ops/topology.py``
``domain_holds``, applied a prefix and a domain) against the sequential
simulation prefix by prefix, in 64 and in 32 bits; its soundness where it
does not claim to be exact; ``_batched_confirm`` with a topology-pending
job against the sequential path's statement op by op; and the solver
reaching a rack-feasible prefix that lies beyond the first 16
capacity-feasible ones, which the fleet-wide verdict could not (the
regression this PR closes).  Racks of 8 in fleets of 64 nodes, seeded.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kai_scheduler_tpu.actions import solvers
from kai_scheduler_tpu.actions.reclaim import survey_reclaim_victims
from kai_scheduler_tpu.framework import propose
from kai_scheduler_tpu.framework.conf import SchedulerConfig
from kai_scheduler_tpu.ops import scenario_batch as sb
from kai_scheduler_tpu.ops import topology as topo
from kai_scheduler_tpu.utils.metrics import METRICS, _key
from kai_scheduler_tpu.utils.tracing import TRACER
from tests.fixtures import build_session, run_action

RACK = 8
SEEDS = (3, 11, 3000000019)
# (required, preferred) of the claimer.
LEVELS = {"rack": ("rack", None), "superpod": ("superpod", None),
          "superpod+rack": ("superpod", "rack")}


def config(strategy: str = "binpack", **settings) -> SchedulerConfig:
    cfg = SchedulerConfig(gpu_placement_strategy=strategy,
                          cpu_placement_strategy=strategy)
    for key, value in settings.items():
        setattr(cfg, key, value)
    return cfg


def node_spec(i: int) -> dict:
    return {"gpu": 8, "cpu": "64", "mem": "512Gi",
            "labels": {"superpod": f"s{i // (4 * RACK)}",
                       "rack": f"r{i // RACK:02d}"}}


def victim_job(node: str, tick: float) -> dict:
    return {"queue": "b", "min_available": 2, "creation_ts": tick,
            "tasks": [{"gpu": 1, "cpu": "4", "mem": "32Gi",
                       "status": "RUNNING", "node": node}
                      for _ in range(4)]}


def whole_node(node: str) -> dict:
    return {"queue": "c", "preemptible": False, "tasks": [
        {"gpu": 8, "cpu": "32", "mem": "256Gi", "status": "RUNNING",
         "node": node}]}


def claimer(pods: list, required, preferred=None) -> dict:
    return {"queue": "a", "min_available": len(pods), "preemptible": False,
            "topology": "mesh", "required_topology_level": required,
            "preferred_topology_level": preferred, "tasks": pods}


def gang_pods(gang: int, master: bool) -> list:
    pod = {"gpu": 1, "mem": "32Gi", "cpu": "4"}
    return [{**pod, "cpu": "8"}] * master + [pod] * (gang - master)


def base_spec() -> dict:
    return {"nodes": {}, "jobs": {}, "queues": {
        "a": {"deserved": {"gpu": 256}}, "b": {"deserved": {"gpu": 8}},
        "c": {"deserved": {"gpu": 256}}},
        "topologies": {"mesh": {"levels": ["superpod", "rack"]}}}


def scattered_spec(seed: int, pods: list, required, preferred=None,
                   nodes: int = 64) -> dict:
    """A full fleet of ``nodes`` nodes in racks of 8, superpods of 4 racks:
    some 60 % of the nodes under two preemptible four-pod jobs each
    (queue ``b``, creation times drawn from the seed, so the victims'
    order runs through the racks at random), the others under a whole-node
    pod."""
    rng = np.random.default_rng([seed, nodes])
    spec = base_spec()
    for i in range(nodes):
        name = f"n{i:04d}"
        spec["nodes"][name] = node_spec(i)
        if rng.random() < 0.6:
            for j in range(2):
                spec["jobs"][f"occ-{i:04d}-{j}"] = victim_job(
                    name, float(rng.integers(0, 1000)))
        else:
            spec["jobs"][f"whole-{i:04d}"] = whole_node(name)
    spec["jobs"]["claimer"] = claimer(pods, required, preferred)
    return spec


def striped_spec(gang: int = 32, stripe: int = 4, master: bool = True,
                 required="rack") -> dict:
    """64 full nodes, 8 racks.  In each of the first ``stripe`` racks four
    nodes are under two victim jobs each; the victims' creation order is
    striped a job at a time over those racks, newest first, so the first
    CAPACITY-feasible prefix for a 32-GPU gang comes after 8 jobs (16
    steps) and the first RACK-feasible one after 29 (58 steps)."""
    spec = base_spec()
    for i in range(64):
        spec["nodes"][f"n{i:04d}"] = node_spec(i)
    tick = 1000.0
    victims = set()
    for k in range(8):                       # job k of every striped rack
        for rack in range(stripe):
            node = rack * RACK + k // 2
            victims.add(node)
            spec["jobs"][f"occ-{rack}-{k}"] = victim_job(f"n{node:04d}",
                                                         tick)
            tick -= 1.0                      # the next one is older
    for i in range(64):
        if i not in victims:
            spec["jobs"][f"whole-{i:04d}"] = whole_node(f"n{i:04d}")
    spec["jobs"]["claimer"] = claimer(gang_pods(gang, master), required)
    return spec


def claimer_tasks(ssn):
    job = ssn.cluster.podgroups["claimer"]
    return job, job.tasks_to_allocate(
        subgroup_order_fn=ssn.pod_set_order_key,
        task_order_fn=ssn.task_order_key, real_allocation=False)


def spy_on_the_prescreen(monkeypatch) -> list:
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


def verdict_and_simulation(ssn, victims: int = 40):
    """(the one call's verdict, the sequential simulation's answer at
    every prefix, the prescreen's span).  The session runs with
    ``batched_scenario_confirm`` off: ``_simulate_attempt`` is then
    ``attempt_to_allocate_job`` under ``subset_nodes``, candidate by
    candidate."""
    assert not ssn.config.batched_scenario_confirm
    job, tasks = claimer_tasks(ssn)
    survey = [pg for pg in survey_reclaim_victims(ssn)
              if pg.queue_id != job.queue_id]
    builder = solvers.ScenarioBuilder(job, tasks, survey[:victims])
    TRACER.begin_cycle(1)
    with TRACER.span("solve:prescreen", kind="solver") as sp:
        verdict = solvers._prescreen_verdict(ssn, tasks, builder, sp)
    TRACER.end_cycle()
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
    return verdict, want, sp


# -- (a) the domain verdict is the sequential simulation's --------------------
@pytest.mark.parametrize("strategy", ("binpack", "spread"))
@pytest.mark.parametrize("master", (False, True),
                         ids=("identical", "master+workers"))
@pytest.mark.parametrize("levels", LEVELS)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_domain_verdict_is_the_sequential_simulations(
        monkeypatch, seed, levels, master, strategy):
    """``verdict[k]`` of the one call equals what ``attempt_to_allocate_job``
    gives under ``subset_nodes`` with the victims of steps 0..k evicted,
    for every k, in 64 bits and in 32 (the same operands with x64 off, as
    the chip runs them).  Exact for a counted gang whatever the levels and
    for a gang of runs under a required level alone; a gang of runs that
    also PREFERS a level keeps the fleet-wide verdict (its boosts move
    where a run lands): sound, and said on the span as no level."""
    sent = spy_on_the_prescreen(monkeypatch)
    required, preferred = LEVELS[levels]
    ssn = build_session(
        scattered_spec(seed, gang_pods(16, master), required, preferred),
        config(strategy, batched_scenario_confirm=False))
    verdict, want, span = verdict_and_simulation(ssn)
    fleet_wide = master and preferred is not None
    assert span.attrs["form"] == ("grouped" if master else "counted")
    assert "declined" not in span.attrs
    assert not want[0] and want[-1]
    sound = all(got or not simulated
                for got, simulated in zip(verdict.tolist(), want))
    assert sound
    (call,) = sent
    if fleet_wide:
        assert span.attrs["level"] == "none" and span.attrs["domains"] == 0
        assert call.verdict.ndim == 1 and not call.kw["named"]
        return
    assert span.attrs["level"] == required
    assert span.attrs["domains"] == (8 if required == "rack" else 2)
    assert verdict.tolist() == want
    assert call.verdict.shape[0] == 2       # seated, and the fleet's
    fleet = call.verdict[1, :len(want)]
    assert span.attrs["pruned"] == int((fleet & ~verdict).sum())
    assert (fleet | ~verdict).all()         # a domain is part of the fleet
    named = call.kw["named"]
    with jax.enable_x64(False):
        narrow = sb.batch_prefix_feasibility(
            *(jnp.asarray(a) for a in call.nodes),
            *(jnp.asarray(a) for a in call.operands),
            **{k: jnp.asarray(v) for k, v in named.items()},
            **{k: v for k, v in call.kw.items()
               if k in ("num_prefixes", "gpu_strategy", "cpu_strategy",
                        "num_domains")})
        assert jnp.asarray(call.nodes[1]).dtype == jnp.float32
    assert np.asarray(narrow)[0, :len(want)].tolist() == verdict.tolist()


@pytest.mark.parametrize("seed", SEEDS + (5, 7, 13))
def test_the_verdict_never_refuses_what_the_simulation_seats(seed):
    """Gangs the exact claim does not cover: pods of three sizes under a
    required superpod and a preferred rack, whose boosts move where each
    run lands: the fleet-wide verdict.  No False where the sequential
    simulation succeeds."""
    rng = np.random.default_rng(seed)
    sizes = [{"gpu": 1, "cpu": "4", "mem": "32Gi"},
             {"gpu": 2, "cpu": "8", "mem": "64Gi"},
             {"gpu": 1, "cpu": "16", "mem": "32Gi"}]
    pods = [sizes[int(i)] for i in rng.integers(0, 3, 12)]
    ssn = build_session(scattered_spec(seed, pods, "superpod", "rack"),
                        config(batched_scenario_confirm=False))
    verdict, want, span = verdict_and_simulation(ssn)
    assert span.attrs["form"] == "grouped" and span.attrs["runs"] > 2
    assert span.attrs["level"] == "none"
    assert any(want)
    assert all(got or not simulated
               for got, simulated in zip(verdict.tolist(), want))


def test_a_job_pinned_by_its_running_pods_is_held_to_their_rack():
    """A claimer with a pod running in rack 1 may only grow there
    (``_pinned_domains``, which the host's candidates and the device's
    ``domain_ok`` both read)."""
    spec = striped_spec(gang=32, master=False)
    spec["jobs"]["claimer"]["tasks"][0] = {
        "gpu": 1, "cpu": "4", "mem": "32Gi", "status": "RUNNING",
        "node": "n0015"}                      # rack 1, beside a whole-node pod
    spec["nodes"]["n0015"]["gpu"] = 9
    ssn = build_session(spec, config(batched_scenario_confirm=False,
                                     max_victims_considered=64))
    verdict, want, span = verdict_and_simulation(ssn, victims=64)
    assert verdict.tolist() == want and any(want)
    job, _tasks = claimer_tasks(ssn)
    _level, _slots, domain_ok, domains, _pref = \
        ssn.required_domain_fns[0](job)
    assert domains == 8 and np.flatnonzero(domain_ok).tolist() == [1]


# -- (b) one definition of "the domain holds the gang" ------------------------
@pytest.mark.parametrize("seed", SEEDS)
def test_the_hosts_candidates_are_domain_holds_of_the_aggregates(seed):
    """``subset_nodes`` offers exactly the domains ``domain_holds`` passes
    on ``domain_aggregates``' sums, whose count of a node is the
    prescreen's ``stack_count`` wherever the quotient is exact."""
    ssn = build_session(scattered_spec(seed, gang_pods(8, True), "rack"))
    job, tasks = claimer_tasks(ssn)
    # Free some GPUs so that a few racks hold the gang.
    stmt = ssn.statement()
    for pg in list(ssn.cluster.podgroups.values())[:30]:
        if pg.queue_id == "b":
            for task in list(pg.pods.values()):
                stmt.evict(task)
    n = len(ssn.snapshot.node_names)
    free = (ssn.node_idle + ssn.node_releasing)[:n]
    reqs = np.stack([ssn._task_row(t)[0] for t in tasks])
    seg = ssn.cluster.topologies and \
        ssn.required_domain_fns[0].__self__.trees["mesh"].node_domain["rack"]
    sums, pods = topo.domain_aggregates(
        jnp.asarray(free), jnp.asarray(ssn.node_room[:n]), jnp.asarray(seg),
        jnp.asarray(reqs.max(axis=0)), float(len(tasks)), 8)
    holds = topo.domain_holds(np.asarray(sums), np.asarray(pods),
                              reqs.sum(axis=0), len(tasks))
    stacked = sb.stack_count(tuple(jnp.asarray(free[:, r])
                                   for r in range(free.shape[1])),
                             jnp.asarray(ssn.node_room[:n]),
                             jnp.asarray(reqs.max(axis=0)))
    want_pods = np.bincount(seg, np.clip(np.asarray(stacked), 0, len(tasks)),
                            minlength=8)
    assert np.asarray(pods).tolist() == want_pods.tolist()
    offered = sorted(int(seg[np.flatnonzero(mask[:n])[0]])
                     for mask in ssn.subset_nodes(job, tasks))
    assert offered == np.flatnonzero(holds).tolist() and offered
    stmt.discard()


def test_domain_slots_lays_a_level_out_a_domain_a_row():
    seg = np.array([2, 0, -1, 2, 1, 2, 0, -1, 1, 2], np.int32)
    slot_node, d_pad = topo.domain_slots(seg, 16)
    table = slot_node.reshape(d_pad, -1)
    assert d_pad == 4 and table.shape == (4, 4)
    assert table.tolist() == [[1, 6, 16, 16], [4, 8, 16, 16],
                              [0, 3, 5, 9], [16, 16, 16, 16]]
    none, d_none = topo.domain_slots(np.full(5, -1, np.int32), 8)
    assert d_none == 1 and none.tolist() == [8]


def test_ragged_domains_decline(monkeypatch):
    """One domain of 40 nodes beside 24 of one node each would make a
    table of 32 x 64 slots for 64 nodes: declined, under its own reason."""
    spec = striped_spec()
    for i, node in enumerate(spec["nodes"].values()):
        node["labels"]["rack"] = "big" if i < 40 else f"r{i}"
    ssn = build_session(spec, config(batched_scenario_confirm=False))
    _verdict, _want, span = verdict_and_simulation(ssn)
    assert span.attrs["declined"] == "ragged-domains"


# -- (c) the confirm takes a topology-pending job -----------------------------
def ops_of(ssn) -> list:
    return [(op.kind, op.task.uid, op.node_name)
            for stmt in ssn.statements if stmt.committed
            for op in stmt.ops]


def reclaim(spec: dict, cfg: SchedulerConfig):
    ssn = build_session(spec, cfg)
    TRACER.begin_cycle(1)
    run_action(ssn, "reclaim")
    return ssn, TRACER.end_cycle()


@pytest.mark.parametrize("master", (False, True),
                         ids=("identical", "master+workers"))
@pytest.mark.parametrize("levels", LEVELS)
def test_the_batched_confirm_is_the_sequential_paths_statement(levels,
                                                               master):
    """The same reclaim with ``batched_scenario_confirm`` on and off: the
    committed statement is the same op for op (evictions, the gang's
    places inside one domain, the prefix's victims placed again), and the
    batched side made ONE multi-job call, and a second for what was left
    of the victims that stand again, where the other made one a job."""
    required, preferred = LEVELS[levels]
    spec = striped_spec(master=master, required=required)
    spec["jobs"]["claimer"]["preferred_topology_level"] = preferred
    settings = dict(max_victims_considered=64)
    batched, trace = reclaim(spec, config(**settings))
    plain, plain_trace = reclaim(spec, config(
        batched_scenario_confirm=False, **settings))
    assert ops_of(batched) == ops_of(plain) and ops_of(batched)
    job = batched.cluster.podgroups["claimer"]
    nodes = {t.node_name for t in job.pods.values()}
    level = "superpod" if required == "superpod" else "rack"
    assert len({batched.cluster.nodes[n].labels[level] for n in nodes}) == 1

    def dispatches(tr, multi: bool):
        return sum(1 for s in tr.spans
                   if s.name.startswith("dispatch:allocate")
                   and not s.name.endswith("_fetch")
                   and ("_multi" in s.name) == multi)
    assert (dispatches(trace, True), dispatches(trace, False)) \
        == (1 + (required == "rack"), 0)
    # One call for the gang and one for each victim past its gates.
    assert dispatches(plain_trace, True) == 0
    assert dispatches(plain_trace, False) > 1
    (solve,) = [s for s in trace.spans if s.name == "solve:job"]
    assert solve.attrs["solved"] and solve.attrs["tried"] == 2
    # Under a required rack the prefix runs through four racks and the
    # three others' victims are placed again.
    assert (solve.attrs["replaced"] > 0) == (required == "rack")


def test_a_podset_constraint_still_goes_the_sequential_way():
    """A podset's own topology constraint: the confirm declines, counted
    under its own reason; the prescreen keeps the fleet-wide verdict."""
    series = _key("batched_form_declined_total",
                  {"form": "confirm", "reason": "podset-topology"})
    spec = striped_spec(gang=8, master=False, required=None)
    spec["jobs"]["claimer"]["pod_sets"] = [
        {"name": "default", "min_available": 8, "topology": "mesh",
         "required_topology_level": "rack"}]
    before = METRICS.counters.get(series, 0)
    ssn, trace = reclaim(spec, config(max_victims_considered=64))
    assert METRICS.counters[series] > before
    spans = [s for s in trace.spans if s.name == "solve:prescreen"]
    assert all(s.attrs.get("level", "none") == "none" for s in spans)
    assert ("confirm", "podset-topology") in propose.DECLINES


# -- (d) the regression: beyond the first 16 capacity-feasible prefixes -------
PRUNED = "scenario_prescreen_domain_pruned_total"
DOMAIN_CALLS = "scenario_prescreen_domain_calls_total"


@pytest.mark.parametrize("master", (False, True),
                         ids=("identical", "master+workers"))
def test_the_solver_reaches_the_rack_feasible_prefix(master):
    """The victims' order is striped over four racks: 32 GPUs are free
    somewhere after 16 steps and in ONE rack after 58.  The solver skips
    to step 58 on the domain verdict and confirms there: two simulated
    scenarios, one prescreen, one confirm."""
    before = {c: METRICS.counters.get(c, 0) for c in (PRUNED, DOMAIN_CALLS)}
    ssn, trace = reclaim(striped_spec(master=master),
                         config(max_victims_considered=64))
    (solve,) = [s for s in trace.spans if s.name == "solve:job"]
    (screen,) = [s for s in trace.spans if s.name == "solve:prescreen"]
    assert solve.attrs["solved"] and solve.attrs["tried"] == 2
    assert screen.attrs["level"] == "rack" and screen.attrs["domains"] == 8
    assert screen.attrs["form"] == ("grouped" if master else "counted")
    # The verdict starts at step 2: index 56 is step 58.
    assert screen.attrs["first_feasible"] == 56
    assert screen.attrs["pruned"] == 58 - 16 > 16
    assert METRICS.counters[PRUNED] - before[PRUNED] == 42
    assert METRICS.counters[DOMAIN_CALLS] - before[DOMAIN_CALLS] == 1
    job = ssn.cluster.podgroups["claimer"]
    racks = {ssn.cluster.nodes[t.node_name].labels["rack"]
             for t in job.pods.values()}
    assert racks == {"r00"}
    # The smallest prefix: 29 jobs, 8 of them in the gang's rack.  Every
    # pod of the 21 others is placed again: a victim's gang chunk, and
    # then its surplus a pod a chunk (``_place_the_rest``), so that exactly
    # the gang's 32 stay evicted.
    evicted = [op for op in ssn.statements[-1].ops if op.kind == "evict"]
    assert len(evicted) == 29 * 4
    assert solve.attrs["replaced"] == 29 * 4 - 32


def test_a_level_blind_verdict_gives_up_after_16_scenarios(monkeypatch):
    """Today's gap, kept as the control: with the domain axis taken away
    the fleet-wide verdict passes every prefix from step 16 on, the solver
    simulates ``max_scenarios_per_job`` of them and the gang is never
    seated."""
    ssn = build_session(striped_spec(), config(max_victims_considered=64))
    monkeypatch.setattr(ssn, "required_domain_fns", [])
    TRACER.begin_cycle(1)
    run_action(ssn, "reclaim")
    trace = TRACER.end_cycle()
    (solve,) = [s for s in trace.spans if s.name == "solve:job"]
    (screen,) = [s for s in trace.spans if s.name == "solve:prescreen"]
    assert screen.attrs["level"] == "none" and "pruned" not in screen.attrs
    assert not solve.attrs["solved"]
    assert solve.attrs["tried"] == ssn.config.max_scenarios_per_job
    job = ssn.cluster.podgroups["claimer"]
    assert not any(t.node_name for t in job.pods.values())


# -- (e) the counters are there from a session's opening ----------------------
@pytest.mark.parametrize("counter", (
    DOMAIN_CALLS, PRUNED, "scenario_prescreen_pool_cells_total",
    "reclaim_victims_examined_total",
    _key("batched_form_declined_total",
         {"form": "confirm", "reason": "podset-topology"}),
    _key("batched_form_declined_total",
         {"form": "confirm", "reason": "victim-topology"})))
def test_a_session_registers_the_counter_at_its_opening(counter):
    METRICS.counters.pop(counter, None)
    build_session(base_spec())
    assert METRICS.counters[counter] == 0


def test_the_solvers_counters_split_what_a_commit_did_with_its_victims():
    """``solver_evictions_total`` and ``solver_victims_replaced_total``,
    the series every action has, under ``action="reclaim"``: what stays
    evicted is their difference, the gang's 32."""
    names = [_key(c, {"action": "reclaim"}) for c in (
        "solver_evictions_total", "solver_victims_replaced_total")]
    before = [METRICS.counters.get(c, 0) for c in names]
    reclaim(striped_spec(), config(max_victims_considered=64))
    moved = [METRICS.counters[c] - b for c, b in zip(names, before)]
    assert moved == [29 * 4, 29 * 4 - 32]
