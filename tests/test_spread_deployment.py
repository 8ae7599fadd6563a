"""The deployment ``spread-98k`` at 64 and 256 nodes, on the CPU: the
benchmark's own client (``benchmark/generators/spread_reclaim_gangs.py``,
which is ``reclaim_gangs``' loop) drives ``Scheduler.run_once`` on a shard
whose two placement strategies are spread, a PyTorchJob a cycle (a master
beside its workers) reclaims its GPUs and is bound a cycle later, and the
plain reference the chip's ``correct`` uses
(``benchmark/reference/spread_eviction.py``, loaded by path, no import of
the program) finds all twelve numbers 0: the reclaim cell's eleven, and
every pod on the node upstream's spread order gives it.  The same run
under bin-pack settings is told apart by that twelfth number alone.  And
what the program says of the batched forms a spread strategy declines (the
fill and the wave, which claim idle) and of the one it does not (the
prescreen's run loop, since PR 43): the counter family
``batched_form_declined_total{form, reason}`` and the ``strategy``
attribute (docs/OBSERVABILITY.md "Span model")."""

import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

from kai_scheduler_tpu.framework import propose
from kai_scheduler_tpu.ops import scenario_batch as sb
from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel
from kai_scheduler_tpu.ops.scoring import BINPACK, SPREAD
from kai_scheduler_tpu.utils.metrics import METRICS, _key
from kai_scheduler_tpu.utils.tracing import TRACER
from tests.prescreen_oracle import scan_prefixes
from tests.test_scenario_batch import traced_forms

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "spread98k-pytorchjob-256"
SEEDS = (3, 11, 3000000019)
STRATEGIES = {"spread": SPREAD, "binpack": BINPACK}
# nodes, share of them under the occupier's preemptible jobs, queue tree,
# whole-node gang, the reclaimer's gang, victims the solver considers.
WIDTHS = {
    64: {"share": 1.0, "departments": 2, "leaves": 2, "whole": 4,
         "gang": 24, "victims": 32},
    256: {"share": 0.25, "departments": 4, "leaves": 4, "whole": 8,
          "gang": 32, "victims": 64},
}


def declined(form: str, reason: str) -> str:
    return _key("batched_form_declined_total",
                {"form": form, "reason": reason})


COUNTERS = tuple(declined(*pair) for pair in propose.DECLINES) + (
    "scenario_prescreen_scan_steps_total", "device_kernel_calls")


def small_cell(nodes: int, strategy: str = "spread"):
    """The cell as ``BENCHMARK.json`` names it (its generator and its
    reference loaded by path, as a chip run loads them) with the fleet,
    the gang and the solver's caps cut to ``nodes``, and both of the
    operator's strategies set to ``strategy``."""
    from benchmark.harness import spec
    from benchmark.tests.control_spread import cut_cell
    cell = spec.Cell(spec.load_benchmark(ROOT), CELL, ROOT)
    assert cell.reference.__file__ == os.path.join(
        BENCH, "reference", "spread_eviction.py")
    assert cell.generator.__file__ == os.path.join(
        BENCH, "generators", "spread_reclaim_gangs.py")
    assert cell.config["scheduler"]["gpu_placement_strategy"] == "spread"
    assert cell.config["scheduler"]["cpu_placement_strategy"] == "spread"
    cut_cell(cell, nodes=nodes, **WIDTHS[nodes])
    cell.config["scheduler"].update(gpu_placement_strategy=strategy,
                                    cpu_placement_strategy=strategy)
    return cell


@pytest.fixture(scope="module", params=[
    (nodes, seed, strategy) for nodes in WIDTHS for seed in SEEDS
    for strategy in STRATEGIES],
    ids=lambda p: f"{p[0]}n-seed{p[1]}-{p[2]}")
def driven(request):
    """Five cycles of the deployment through ``Scheduler.run_once``, the
    last cycle's trace, and what each prescreen was sent and answered."""
    from kai_scheduler_tpu.actions import solvers
    nodes, seed, strategy = request.param
    cell = small_cell(nodes, strategy)
    sent = []
    run_on_nodes = propose.run_on_nodes

    def spy(ssn, kernel, operands, **kw):
        verdict = run_on_nodes(ssn, kernel, operands, **kw)
        # Copies: the session's device arrays are patched in place later.
        sent.append(types.SimpleNamespace(
            nodes=tuple(np.array(a) for a in ssn._device_arrays()),
            operands=operands, static=kw, verdict=np.array(verdict)))
        return verdict

    patch = pytest.MonkeyPatch()
    patch.setattr(solvers.propose, "run_on_nodes", spy)
    try:
        TRACER.reset()
        client = cell.generator.build(cell, seed, counters=COUNTERS)
        for _ in range(5):
            client.cycle()
    finally:
        patch.undo()
    return types.SimpleNamespace(
        cell=cell, client=client, nodes=nodes, strategy=strategy,
        sent=sent, trace=TRACER.get_trace(),
        verdict=cell.generator.compare(client.records[1:], client.ledger,
                                       cell))


def test_the_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(BENCH, "reference", "spread_eviction.py")
    tree = ast.parse(open(path).read())
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names} | {
        n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert imported == {"__future__", "math", "numpy"}


def test_every_count_but_the_strategys_is_zero(driven):
    """Under spread all twelve numbers are 0; under bin-pack settings the
    comparison tells the strategies apart, by where pods landed and by
    nothing else."""
    out, cell = driven.verdict, driven.cell
    assert list(out["compared"]) == list(cell.generator.LIMITS)
    assert len(out["compared"]) == 12
    moved = {k: v[0] for k, v in out["compared"].items() if v[0]}
    if driven.strategy == "spread":
        assert out["correct"] and not moved, out["compared"]
    else:
        assert not out["correct"]
        assert set(moved) == {"placements_not_reference"}
        assert moved["placements_not_reference"] > 0
    gang = WIDTHS[driven.nodes]["gang"]
    # One reclaim and one bind in every cycle once the first gang waits.
    assert out["failed"] == 0 and out["attempted"] == 3
    assert out["run"]["evictions_per_cycle"] == [gang]
    assert out["run"]["binds_per_cycle"] == [gang]
    assert out["run"]["prescreens_per_cycle"] == [1]
    assert out["run"]["bind_cycles_after_arrival"] == [1]
    assert out["run"]["placements_checked"] == out["bound_pods"] == 4 * gang


def test_the_placements_are_the_references_pod_by_pod(driven):
    """Every bound gang against ``place_gang`` from the ledger before its
    cycle: equal node for node under spread, and under bin-pack the gang
    sits on fewer nodes than the spread order would give it."""
    client, ref = driven.client, driven.cell.reference
    ledger = client.ledger
    checked = 0
    for rec in client.records[1:]:
        for gang in rec.pending:
            bound = rec.bound.get(gang.uid)
            if not bound:
                continue
            got = np.array([bound[name] for name in gang.names])
            want = ref.place_gang(ledger.capacity, rec.used_before,
                                  rec.pods_before, ledger.max_pods, gang.req)
            checked += 1
            # The master is the first row, and asks more than a worker.
            assert gang.req[0, 0] == 2 * gang.req[1, 0]
            if driven.strategy == "spread":
                assert got.tolist() == want.tolist()
            else:
                assert got.tolist() != want.tolist()
                assert len(set(got.tolist())) <= len(set(want.tolist()))
    assert checked == 4


def test_the_mixed_gang_is_grouped_under_spread_and_under_binpack(
        driven, monkeypatch):
    """``dispatched_form`` of the rows the solver sent, by the host; and
    the branch the program traces for the same rows and strategies, by
    the device: one form on both sides, the run loop under either strategy
    (under spread the exact scan, until PR 43)."""
    strategy = STRATEGIES[driven.strategy]
    gang = WIDTHS[driven.nodes]["gang"]
    call = driven.sent[-1]
    rows = call.operands[3:]
    t_pad = len(rows[0])
    assert t_pad >= gang and t_pad & (t_pad - 1) == 0
    assert sb.dispatched_form(*rows) == ("grouped", 2)
    assert call.static["gpu_strategy"] == call.static["cpu_strategy"] \
        == strategy
    # A mask that tells no two pods apart changes neither the form nor
    # the runs (it sent any rows to the exact scan until PR 45).
    n = len(call.nodes[0])
    assert sb.dispatched_form(*rows, np.ones((t_pad, n), bool)) \
        == ("grouped", 2)
    masters = np.ones((t_pad, n), bool)
    masters[:2, 0] = False           # the master and the first worker
    assert sb.dispatched_form(*rows, masters) == ("grouped", 3)
    (span,) = [s for s in driven.trace.spans if s.name == "solve:prescreen"]
    assert span.attrs["form"] == "grouped"
    assert span.attrs["strategy"] == driven.strategy
    assert span.attrs["runs"] == 2
    traced = traced_forms(monkeypatch)
    sb.batch_prefix_feasibility.clear_cache()
    try:
        again = sb.batch_prefix_feasibility(
            *map(jnp.asarray, call.nodes), *map(jnp.asarray, call.operands),
            num_prefixes=call.static["num_prefixes"],
            gpu_strategy=strategy, cpu_strategy=strategy)
    finally:
        sb.batch_prefix_feasibility.clear_cache()
    # The cond holds the counted branch and the run loop, keyed by the
    # call's strategies.
    assert sorted(traced) == ["count_prefixes", "group_prefixes"]
    assert np.asarray(again).tolist() == call.verdict.tolist()


def test_the_verdict_bits_equal_as_many_sequential_simulations(driven):
    """The prescreen's K bits against K calls of the exact kernel, one a
    prefix, each on that prefix's releasing pool made here in numpy: the
    pending job's pipeline-only attempt, under the shard's strategy."""
    strategy = STRATEGIES[driven.strategy]
    call = driven.sent[-1]
    alloc, idle, rel, labels, taints, room = call.nodes
    step, node, vec, task_req, task_job, task_sel, task_tol = call.operands
    k = call.static["num_prefixes"]
    delta = np.zeros((k + 1,) + rel.shape)
    np.add.at(delta, (np.minimum(step, k), node), vec)
    pools = rel[None] + np.cumsum(delta[:k], axis=0)
    bits = []
    for pool in pools:
        result = allocate_jobs_kernel(
            alloc, idle, pool, labels, taints, room, task_req, task_job,
            task_sel, task_tol, jnp.array([True, False]),
            gpu_strategy=strategy, cpu_strategy=strategy,
            pipeline_only=True)
        bits.append(bool(result.job_success[0]))
    assert bits == call.verdict.tolist()
    # Both answers are there, and the first feasible prefix is the step
    # that frees a GPU a pod: two pods a step, the first step simulated.
    gang = WIDTHS[driven.nodes]["gang"]
    assert bits.index(True) == gang // 2 - 2
    assert all(bits[gang // 2 - 2:len(bits)])


def test_the_family_counts_once_a_call_by_form_and_reason(driven):
    """A spread shard declines the grouped fill twice a cycle (the bind,
    and the attempt that finds the fleet full), by its strategy; under
    bin-pack the same two calls decline the fill by their rows (a master
    beside its workers).  The prescreen's run loop answers under both, in
    two steps (since PR 43; a spread shard declined it once a cycle and
    took a step a padded row).  Never once a task, and every series is
    there from the first session on."""
    spread = driven.strategy == "spread"
    for rec in driven.client.records[1:]:
        want = {declined(*pair): 0 for pair in propose.DECLINES}
        want[declined("grouped_fill", "strategy" if spread else "rows")] = 2
        want["scenario_prescreen_scan_steps_total"] = 2
        want["device_kernel_calls"] = 5
        assert rec.counters == want
    first = driven.client.records[0].counters
    assert set(first) == set(COUNTERS)
    assert first[declined("wave", "strategy")] == 0


def test_the_prescreens_series_is_registered_at_0_and_a_solve_moves_it_not(
        driven):
    """``prescreen_runs``/``strategy`` stays in the family for the
    benchmark's ``strategy_declines``, which reads it in every cell: there
    from the first session on, at 0, and 0 after every cycle's solve under
    either strategy, each of which asked the prescreen."""
    key = declined("prescreen_runs", "strategy")
    assert ("prescreen_runs", "strategy") in propose.DECLINES
    assert [rec.counters[key] for rec in driven.client.records] == [0] * 5
    assert len(driven.sent) == 5                 # a prescreen a cycle
    before = METRICS.counters[key]
    propose.register_declines()
    assert METRICS.counters[key] == before


def test_the_spans_say_the_strategy(driven):
    spans = driven.trace.spans
    operands = [s for s in spans if s.name == "propose:operands"]
    # The bind, the attempt, and the two confirms.
    assert len(operands) == 4
    assert {s.attrs["strategy"] for s in operands} == {driven.strategy}
    assert [s.attrs["path"] for s in operands] == ["exact", "exact",
                                                   "multi", "multi"]
    assert not [s for s in spans if s.name.startswith(
        "dispatch:allocate_grouped")]


@pytest.mark.parametrize("gpu, cpu, name", [
    ("binpack", "binpack", "binpack"), ("spread", "spread", "spread"),
    ("spread", "binpack", "mixed"), ("binpack", "spread", "mixed")])
def test_the_strategy_attribute_names_both_axes(gpu, cpu, name):
    ssn = types.SimpleNamespace(gpu_strategy=STRATEGIES[gpu],
                                cpu_strategy=STRATEGIES[cpu])
    assert propose.strategy_name(ssn) == name
    # A master beside three workers: two runs under any pair, which
    # chooses the key each run lands by and never the form.
    req = np.tile([4000.0, 2.0 ** 35, 1.0], (4, 1))
    req[0] *= 2
    rows = (req, np.zeros(4, np.int32), np.full((4, 1), -1, np.int32),
            np.full((4, 1), -1, np.int32))
    assert sb.dispatched_form(*rows) == ("grouped", 2)
    n = 6
    none = np.full((n, 1), -1, np.int32)
    pool = np.tile(2 * req[1], (2, n, 1))
    pool[0, 1:] = 0.0                  # prefix 0: one node, of two workers
    fleet = tuple(map(jnp.asarray, (np.tile(8 * req[1], (n, 1)),
                                    np.zeros((n, 3)), none, none,
                                    np.full(n, 4.0), *rows)))
    strategies = (ssn.gpu_strategy, ssn.cpu_strategy)
    want = scan_prefixes(jnp.asarray(pool), *fleet, None, *strategies)
    assert np.asarray(want).tolist() == [False, True]
    got = sb.group_prefixes(
        jnp.asarray(pool), *fleet, gpu_strategy=strategies[0],
        cpu_strategy=strategies[1])
    assert np.asarray(got).tolist() == [False, True]


def test_the_wave_declines_a_spread_shard_once_a_call():
    """``wave_filter`` under spread takes no job and says so once, however
    many jobs wait; under bin-pack it counts nothing."""
    key = declined("wave", "strategy")
    propose.register_declines()
    ssn = types.SimpleNamespace(gpu_strategy=SPREAD, cpu_strategy=BINPACK,
                                term_carriers=())
    before = METRICS.counters[key]
    assert propose.wave_filter(ssn) is None
    assert METRICS.counters[key] == before + 1
    ssn.gpu_strategy = BINPACK
    assert propose.wave_filter(ssn) is not None
    assert METRICS.counters[key] == before + 1


@pytest.mark.parametrize("reason", ("domain_rows", "rows", "extras", "mask"))
def test_the_grouped_fill_says_why_it_declines_under_binpack(reason):
    """The reasons ``_grouped_fill_rows`` already tested, now counted:
    one a call, the first that holds."""
    n = 6
    ssn = types.SimpleNamespace(gpu_strategy=BINPACK, cpu_strategy=BINPACK)
    req = np.tile([4000.0, 2.0 ** 35, 1.0], (4, 1))
    if reason == "rows":
        req[0] *= 2
    rows = propose.TaskOperands(
        req, np.zeros(4, np.int32), np.full((4, 1), -1, np.int32),
        np.full((4, 1), -1, np.int32), np.ones(2, bool), 4, 4, 2)
    extra = mask = None
    if reason == "extras":
        extra = np.zeros((4, n))
        extra[0, 1] = 5.0                # no tier constant, and one row's
    if reason == "mask":
        mask = np.ones((4, n), bool)
        mask[2, 3] = False
    propose.register_declines()
    before = dict(METRICS.counters)
    assert propose._grouped_fill_rows(
        ssn, rows, extra, mask, None, reason == "domain_rows") is None
    moved = {k: v - before[k] for k, v in METRICS.counters.items()
             if "batched_form_declined_total" in k and v != before[k]}
    assert moved == {declined("grouped_fill", reason): 1}
    # The same rows with nothing in the way take the fill, and count none.
    before = dict(METRICS.counters)
    req[:] = req[1]
    assert propose._grouped_fill_rows(ssn, rows, None, None, None,
                                      False) == (None, None)
    assert {k for k, v in METRICS.counters.items()
            if "batched_form_declined_total" in k and v != before[k]} \
        == set()


# -- what the width showed: a fair share in 32 bits ---------------------------
# The chip computes the fair share in f32.  At 98,304 nodes the reclaimer's
# department asks 590,852,000 milli-cores, which f32 holds as 590,851,968:
# the validator found the department 32 milli-cores over its share and
# refused every scenario (PERF.md section 6, PR 42).  The sums below are
# that department's, with gangs of a master beside w workers.
HELD = 3 * 6144 * 32000.0            # three leaves of whole-node pods


@pytest.mark.parametrize("workers", range(1, 9))
def test_a_share_that_is_the_request_in_32_bits_is_the_request(workers):
    from kai_scheduler_tpu.ops import fairshare as fsops
    asked = HELD + 8000.0 + 4000.0 * workers
    request = np.array([[asked, 2.0 ** 40, 6.0 + workers]])
    deserved = np.array([[2 * HELD, 2.0 ** 50, 64.0]])
    limit = np.full((1, 3), fsops.UNLIMITED)
    device = request.astype(np.float32)          # the kernel's answer
    assert (float(device[0, 0]) != asked) == (workers % 2 == 1)
    fair = fsops.restore_exact(device, deserved, limit, request)
    assert fair.dtype == np.float64 and fair.tolist() == request.tolist()
    # Deserved is a landing too, and a limit caps what may be asked.
    assert fsops.restore_exact(deserved.astype(np.float32), deserved, limit,
                               2 * deserved).tolist() == deserved.tolist()
    capped = np.array([[asked - 4000.0, 2.0 ** 39, 4.0]])
    assert fsops.restore_exact(capped.astype(np.float32), deserved, capped,
                               request).tolist() == capped.tolist()
    # An answer that is neither stays what the device said; f64 passes.
    other = np.array([[asked - 1000.0, 3.0, 5.0]], np.float32)
    assert fsops.restore_exact(other, deserved, limit, request).tolist() \
        == other.astype(np.float64).tolist()
    assert fsops.restore_exact(request, deserved, limit, request) is request


def wide_department(workers: int):
    """A fleet of seven nodes whose sums are the 98,304-node fleet's:
    department A holds three nodes of 196,608 cores under one pod each and
    its fourth leaf gets a PyTorchJob; department B's one leaf holds every
    other GPU under preemptible jobs of four one-GPU pods."""
    from kai_scheduler_tpu.api import (ClusterInfo, NodeInfo, PodGroupInfo,
                                       PodInfo, PodStatus, QueueInfo,
                                       QueueQuota)
    from kai_scheduler_tpu.api import resources as rs
    from kai_scheduler_tpu.api.resources import ResourceRequirements
    gib = 2.0 ** 30
    nodes, podgroups = {}, {}

    def running(job, queue, node, count, rr, preemptible, minimum):
        pg = PodGroupInfo(job, job, queue_id=queue, min_available=minimum,
                          preemptible=preemptible)
        for k in range(count):
            task = PodInfo(uid=f"{job}-{k}", name=f"{job}-{k}", res_req=rr,
                           status=PodStatus.RUNNING, node_name=node)
            pg.add_task(task)
            nodes[node].add_task(task)
        podgroups[job] = pg

    big = ResourceRequirements.from_spec("196608", "256Gi", 2)
    one = ResourceRequirements.from_spec("4", "32Gi", 1)
    for i in range(3):
        nodes[f"big-{i}"] = NodeInfo(
            f"big-{i}", np.array([196608000.0, 512 * gib, 2.0]))
        running(f"whole-{i}", f"a{i + 1}", f"big-{i}", 1, big, False, 1)
    for i in range(3):
        nodes[f"v-{i}"] = NodeInfo(f"v-{i}",
                                   np.array([64000.0, 512 * gib, 8.0]))
        for j in range(2):
            running(f"occ-{i}{j}", "b0", f"v-{i}", 4, one, True, 2)
    nodes["cpu-0"] = NodeInfo("cpu-0", np.array([1e9, 512 * gib, 0.0]))
    total = sum(n.allocatable for n in nodes.values())
    queues = {}
    for dep, leaves in (("A", ["a0", "a1", "a2", "a3"]), ("B", ["b0"])):
        queues[dep] = QueueInfo(dep, quota=QueueQuota.from_spec(
            deserved=total / 2))
        for leaf in leaves:
            queues[leaf] = QueueInfo(leaf, parent=dep,
                                     quota=QueueQuota.from_spec(
                                         deserved=total / 2 / len(leaves)))
            queues[dep].children.append(leaf)
    gang = PodGroupInfo("gang", "gang", queue_id="a0",
                        min_available=1 + workers)
    for k, spec_ in enumerate([("8", "64Gi")] + [("4", "32Gi")] * workers):
        gang.add_task(PodInfo(uid=f"gang-{k}", name=f"gang-{k}",
                              res_req=ResourceRequirements.from_spec(
                                  *spec_, 1)))
    podgroups["gang"] = gang
    return ClusterInfo(nodes, podgroups, queues, topologies={}, now=1000.0)


@pytest.mark.parametrize("workers, restored, evicted", [
    (3, True, 4), (3, False, 0), (1, False, 2), (7, True, 8)],
    ids=("down-restored", "down-as-the-device-said", "up-as-the-device-said",
         "down-restored-8"))
def test_a_reclaimer_at_its_share_is_reclaimed_for_in_32_bits(
        monkeypatch, workers, restored, evicted):
    """One cycle with the fair share as a 32-bit device answers it (the
    kernel's f64 answer narrowed to f32 where it comes back: the guard's
    worker thread does not inherit ``jax.enable_x64(False)``, so the
    regime is put on at the seam).  A department sum that f32 rounds down
    (a master beside 3 or 7 workers) refused every scenario while the
    validator read the device's answer as it came; one that f32 rounds up
    (1 worker) never showed it."""
    from kai_scheduler_tpu.framework.conf import SchedulerConfig
    from kai_scheduler_tpu.ops import fairshare as fsops
    from kai_scheduler_tpu.scheduler import Scheduler
    forest = fsops.fair_share_forest
    monkeypatch.setattr(
        fsops, "fair_share_forest",
        lambda *a, **kw: forest(*a, **kw).astype(np.float32))
    if not restored:
        monkeypatch.setattr(fsops, "restore_exact",
                            lambda fair, *_landings: np.asarray(fair))
    cluster = wide_department(workers)
    sched = Scheduler(lambda: cluster, SchedulerConfig(
        gpu_placement_strategy="spread", cpu_placement_strategy="spread"))
    sched.run_once()
    asked = HELD + 8000.0 + 4000.0 * workers
    assert (float(np.float32(asked)) < asked) == (workers in (3, 7))
    assert len(sched.cache.evicted) == evicted
    assert {pod[:3] for pod in sched.cache.evicted} <= {"occ"}
