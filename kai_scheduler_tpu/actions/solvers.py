"""Scenario solvers: victim accumulation + simulated eviction/re-allocation.

Mirrors pkg/scheduler/actions/common/solvers/ (JobSolver.Solve
job_solver.go:47-90, PodAccumulatedScenarioBuilder pod_scenario_builder.go:
33-147, byPodSolver by_pod_solver.go:63-239): to place a pending job at the
expense of running work, victims are accumulated one job at a time from an
ordered queue; each scenario is simulated on the live session under a
statement — evict the victims, pipeline the pending job onto the released
resources, try to re-place victims elsewhere — then validated by the
plugins' scenario validators (DRF post-state, min-runtime, consolidation's
all-replaced rule).  Success commits; failure rolls back and the builder
grows the scenario.

The simulation batches each re-allocation attempt through the device kernel
(the "does this scenario fit" inner loop of SURVEY.md §7.6).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..api import resources as rs
from ..api.cluster_info import EXACT_BELOW
from ..api.pod_status import PodStatus
from ..api.podgroup_info import PodGroupInfo
from ..framework import propose
from ..ops.allocate_grouped import _next_pow2
from ..utils.metrics import METRICS
from ..utils.tracing import TRACER
from .allocate import attempt_to_allocate_job


@dataclass
class Scenario:
    pending_job: PodGroupInfo
    pending_tasks: list
    victims: list = field(default_factory=list)  # [(job, [tasks])]

    def victim_task_count(self) -> int:
        return sum(len(ts) for _, ts in self.victims)


class ScenarioBuilder:
    """Accumulate victims one step at a time (pod_scenario_builder.go:79).

    Elastic victims shrink before they die (proportion.getVictimResources
    splitVictimTasks): a job running above its gang minimum first offers
    only its surplus tasks; the core gang joins the scenario in a later
    step if the surplus wasn't enough.
    """

    def __init__(self, pending_job: PodGroupInfo, pending_tasks: list,
                 ordered_victims: list[PodGroupInfo],
                 offers: "VictimOffers | None" = None):
        self.scenario = Scenario(pending_job, pending_tasks)
        self._steps: list = []
        if offers is None:
            offers = VictimOffers.read(ordered_victims)
        for victim, elastic, core in zip(ordered_victims, offers.elastic,
                                         offers.core):
            if elastic:
                self._steps.append((victim, elastic))
            if core:
                self._steps.append((victim, core))

    def has_next(self) -> bool:
        return bool(self._steps)

    def next_scenario(self) -> Scenario:
        victim, tasks = self._steps.pop(0)
        for i, (vjob, vtasks) in enumerate(self.scenario.victims):
            if vjob.uid == victim.uid:
                self.scenario.victims[i] = (vjob, vtasks + tasks)
                break
        else:
            self.scenario.victims.append((victim, tasks))
        return self.scenario


def _split_victim_tasks(victim: PodGroupInfo):
    """(elastic surplus tasks, core gang tasks), newest surplus first."""
    elastic, core = [], []
    for ps in victim.pod_sets.values():
        active = sorted(
            (t for t in ps.pods.values() if t.is_active_allocated()),
            key=lambda t: (t.name, t.uid))
        surplus = len(active) - ps.min_available
        if surplus > 0:
            elastic.extend(active[ps.min_available:])
            core.extend(active[:ps.min_available])
        else:
            core.extend(active)
    return elastic, core


def victim_offer(victim: PodGroupInfo) -> tuple:
    """What one victim job offers a solve, read off its pods in one walk:
    ``(elastic, core, reqs)``, the split ``ScenarioBuilder`` steps through
    and the ``[pods, R]`` rows the victim adds to ``_within_budget``.  It
    holds until a statement that touched the job commits (a discarded
    statement puts every pod back as it was)."""
    elastic, core = _split_victim_tasks(victim)
    # The split holds every active pod of the job once (each pod is
    # indexed in one pod set), so its requests are the job's.
    reqs = np.array([t.res_req.to_vec(mig_as_gpu=False)
                     for t in elastic + core]).reshape(-1, rs.NUM_RES)
    # An empty part is the one empty tuple: a thousand empty lists kept
    # for a solve's length are a thousand objects the collector carries.
    return elastic or (), core or (), reqs


class VictimOffers:
    """The offers of one solve's victims, in the victims' order: a column
    each of ``victim_offer``'s three parts and no object a victim, so that
    a solve leaves the collector the lists the split always made and
    nothing more.  ``solve_job`` reads them off the victims itself; a
    caller that solves several jobs a cycle over the same victims keeps
    each victim's offer and hands the solver these columns."""

    def __init__(self):
        self.elastic: list = []
        self.core: list = []
        self.reqs: list = []

    def append(self, elastic: list, core: list, reqs: np.ndarray) -> None:
        self.elastic.append(elastic)
        self.core.append(core)
        self.reqs.append(reqs)

    @classmethod
    def read(cls, victims) -> "VictimOffers":
        offers = cls()
        for victim in victims:
            offers.append(*victim_offer(victim))
        return offers


@dataclass
class SolverResult:
    success: bool
    evicted_jobs: list = field(default_factory=list)
    scenarios_tried: int = 0
    scenarios_skipped: int = 0   # passed over on the prescreen's verdict
    replaced: int = 0            # evicted tasks pipelined a place elsewhere


def fractional_headroom(ssn) -> float:
    """Whole-GPU-axis capacity recoverable by repacking live sharing
    groups: each group charges one whole backing device, so the device
    capacity not pinned by ACTIVE members bounds how many devices
    perfect defragmentation could empty.  active_fraction() (not
    used_fraction) so a mixed group's releasing members — whose space
    frees on its own — still count toward the bound; fully-releasing
    groups are skipped since their device already counts in
    node_releasing.  Memoized on the session mutation tick: the bound
    feeds prechecks that run per pending job per cycle."""
    cached = getattr(ssn, "_frac_headroom_cache", None)
    if cached is not None and cached[0] == ssn.mutation_count:
        return cached[1]
    headroom = 0.0
    for node in ssn.cluster.nodes.values():
        for g in node.gpu_sharing_groups.values():
            if g.pods and not g.releasing:
                headroom += max(0.0, 1.0 - g.active_fraction())
    ssn._frac_headroom_cache = (ssn.mutation_count, headroom)
    return headroom


def solve_job(ssn, pending_job: PodGroupInfo,
              ordered_victims: list[PodGroupInfo],
              validate, action_name: str,
              require_all_victims_replaced: bool = False,
              try_replace_victims: bool = True,
              offers: VictimOffers | None = None) -> SolverResult:
    """Find the smallest victim prefix whose eviction lets pending_job
    schedule, validated by ``validate(scenario)``.  Commits on success.

    ``offers``: those of ``ordered_victims``, which is then no longer
    than ``max_victims_considered``, from a caller that already holds
    them (the preempt action's ledger); absent, they are read off the
    victims here."""
    tasks = pending_job.tasks_to_allocate(
        subgroup_order_fn=ssn.pod_set_order_key,
        task_order_fn=ssn.task_order_key, real_allocation=False)
    if not tasks:
        return SolverResult(False)
    ordered_victims = ordered_victims[:ssn.config.max_victims_considered]
    # The span of one reclaimer (or preemptor): the precheck, every
    # simulated scenario, the prescreen and the commit lie under it.
    with TRACER.span("solve:job", kind="solver", job=pending_job.name,
                     action=action_name, tasks=len(tasks),
                     victims=len(ordered_victims)) as sp:
        result = _solve(ssn, pending_job, tasks, ordered_victims, offers,
                        validate, action_name, require_all_victims_replaced,
                        try_replace_victims, sp)
        sp.set(tried=result.scenarios_tried,
               skipped=result.scenarios_skipped, solved=result.success,
               replaced=result.replaced)
    if result.scenarios_skipped:
        METRICS.inc("scenarios_skipped_by_prescreen_total",
                    result.scenarios_skipped)
    return result


def _within_budget(ssn, tasks, offers) -> bool:
    """Cheap infeasibility precheck: even evicting every candidate victim
    cannot create more than (idle + releasing + victim resources +
    repackable fraction headroom); a pending job larger than that can
    never be solved — skip simulating.  The headroom term matters
    because a fractional victim's request vector (0.4 GPU) understates
    what its relocation can free (the WHOLE backing device empties once
    the sharing group drains).  It is never negative and its walk visits
    every node, so it is asked for only where the budget without it
    refuses: a budget that covers the job covers it with more."""
    total_req = np.sum([t.res_req.to_vec(mig_as_gpu=False)
                        for t in tasks], axis=0)
    base = ssn.node_idle.sum(axis=0) + ssn.node_releasing.sum(axis=0)
    if _covers(base, offers, total_req):
        return True
    base[rs.RES_GPU] += fractional_headroom(ssn)
    return _covers(base, offers, total_req)


def _covers(base, offers, total_req) -> bool:
    """Whether ``base`` and what the victims offer cover ``total_req``.

    The victims' term is one sum over the offers' rows, a pod each, where
    it used to be added to ``base`` pod by pod.  In a column whose every
    addend is a whole number and whose total stays under 2**53 every
    partial sum is a whole number a float64 holds, whatever the order:
    there the budget is the pod-by-pod one to the bit, and so is the
    verdict.  In any other
    column (fractional GPUs, an idle fleet's bytes past 2**53) two orders
    may round apart, each by at most n ulps of the total over n addends;
    ``room`` covers both, so there the test may let through a job that
    the pod-by-pod sum would have refused by a rounding (it is then
    simulated and fails), and never refuses one that sum admits."""
    if not offers.reqs:
        return not np.any(total_req > base + 1e-9)
    rows = np.concatenate(offers.reqs)
    offered = rows.sum(axis=0)
    size = np.abs(base) + offered
    exact = ((base == np.floor(base)) & (size < EXACT_BELOW)
             & (rows == np.floor(rows)).all(axis=0))
    room = np.where(exact, 0.0, 2.0 * (len(rows) + 1)
                    * np.finfo(np.float64).eps * size)
    return not np.any(total_req > base + offered + room + 1e-9)


def _solve(ssn, pending_job, tasks, ordered_victims, offers, validate,
           action_name: str, require_all_victims_replaced: bool,
           try_replace_victims: bool, sp) -> SolverResult:
    """``solve_job`` under its span ``sp``."""
    with TRACER.span("solve:precheck", kind="solver"):
        if offers is None:
            offers = VictimOffers.read(ordered_victims)
        if not _within_budget(ssn, tasks, offers):
            return SolverResult(False)

    # Let plugins snapshot pre-simulation state for their validators.
    ssn.on_job_solution_start()

    builder = ScenarioBuilder(pending_job, tasks, ordered_victims, offers)
    sp.set(steps=len(builder._steps))
    # LAZY batched pre-screen: the common reclaim succeeds on its first
    # or second scenario, where a prescreen kernel call is pure overhead
    # (measured 0.69x at 400-queue contention).  Only after
    # ``prescreen_after`` simulated scenarios have FAILED — proof the
    # victim queue is deeply contended — does one device call score every
    # remaining prefix's feasibility, letting the loop skip hopeless
    # prefixes without per-scenario simulation round trips
    # (SURVEY §7.6 — worst-case reclaim latency was scenario-count-bound).
    prescreen = None
    prescreen_offset = 0
    failures = 0
    tried = 0
    skipped = 0
    step_idx = 0
    # One statement across scenarios: evictions accumulate incrementally
    # (by_pod_solver keeps recorded victims evicted and rolls back only
    # the allocation attempt); the attempt itself is checkpointed.
    stmt = ssn.statement()
    while builder.has_next() and tried < ssn.config.max_scenarios_per_job:
        scenario = builder.next_scenario()
        step_idx += 1
        if prescreen is not None:
            k = step_idx - 1 - prescreen_offset
            if 0 <= k < len(prescreen) and not prescreen[k]:
                # The pending job cannot place even with this whole
                # prefix released; simulating would fail identically.
                skipped += 1
                continue
        # Validators depend only on the scenario's composition (victim
        # resources vs queue shares, min-runtimes) — check them BEFORE
        # paying for placement simulation.  Cheap validation rejections do
        # not consume the simulation budget.
        if not validate(scenario):
            continue
        tried += 1
        METRICS.inc("scenarios_simulation_by_action", action=action_name)
        with TRACER.span("solve:scenario", kind="solver",
                         prefix=step_idx) as scenario_span:
            # Evict any victims added since the last simulated scenario.
            new_tasks = _unevicted_tasks(scenario, stmt)
            for task in new_tasks:
                stmt.evict(task)
            cp = stmt.checkpoint()
            ok = _simulate_attempt(ssn, stmt, scenario,
                                   require_all_victims_replaced,
                                   try_replace_victims)
            scenario_span.set(evicted=len(new_tasks), fits=ok)
            if not ok:
                stmt.rollback(cp)
        if ok:
            evicted = {op.task.uid for op in stmt.ops if op.kind == "evict"}
            # A victim the same statement pipelined elsewhere lands again.
            replaced = sum(1 for op in stmt.ops if op.kind == "pipeline"
                           and op.task.uid in evicted)
            with TRACER.span("statement:commit", kind="commit") as commit:
                commit.set(binds=len(stmt.commit()), evictions=len(evicted))
            METRICS.inc("solver_evictions_total", len(evicted),
                        action=action_name)
            METRICS.inc("solver_victims_replaced_total", replaced,
                        action=action_name)
            return SolverResult(True,
                                [vj.uid for vj, _ in scenario.victims],
                                tried, skipped, replaced)
        failures += 1
        if prescreen is None and builder.has_next() \
                and failures >= ssn.config.scenario_prescreen_after:
            # Node mirrors already include this statement's accumulated
            # evictions, so prefix feasibility composes on top of them.
            prescreen = _prefix_prescreen(ssn, tasks, builder)
            prescreen_offset = step_idx
    stmt.discard()
    return SolverResult(False, scenarios_tried=tried,
                        scenarios_skipped=skipped)


def _prefix_prescreen(ssn, tasks, builder: "ScenarioBuilder"):
    """[S] bool per victim-prefix step, from ONE batched kernel call —
    or None when the pending job needs state the batch cannot model.

    Soundness: a False must mean the sequential simulation would also
    fail.  That holds when the pending job's feasibility depends on
    capacity (evictions can then only ADD releasing capacity) and on
    nothing else that an eviction changes.  Host-state tasks
    (fractional/MIG/DRA) disqualify.  So does a STATE-DEPENDENT hard mask
    (host ports in use, bound PVCs, storage, inter-pod terms) and any
    in-gang domain row: such a mask only relaxes as victims leave, so the
    current state's may be stricter than the one the simulation would
    meet, and the batch must not over-prune.  A STATIC mask (required
    node affinity: node labels and names alone, ``Session
    .compute_static_mask``) does not disqualify: the sequential
    simulation applies the same rows to the same nodes and no eviction
    changes a label, so it goes to the kernel as ``task_node_mask`` and
    the verdict stays exact.

    A job-level REQUIRED topology level is state-dependent too, and does
    not disqualify: its candidate domains only grow as victims leave, and
    the kernel applies ``subset_nodes``' own rule to every prefix's state
    (``ops/scenario_batch.py`` ``domain_verdicts``).  A preferred level
    alone changes scores and not feasibility, and a podset's own
    constraint is not modelled: both keep the fleet-wide verdict, which
    is sound for them (a gang that fits a domain fits the fleet).
    """
    with TRACER.span("solve:prescreen", kind="solver") as sp:
        verdict = _prescreen_verdict(ssn, tasks, builder, sp)
        if verdict is not None and len(verdict):
            feasible = np.flatnonzero(verdict)
            sp.set(feasible=int(feasible.size),
                   first_feasible=int(feasible[0]) if feasible.size else -1)
            METRICS.inc("scenario_prescreen_prefixes_total", len(verdict))
            METRICS.inc("scenario_prescreen_feasible_total",
                        int(feasible.size))
        return verdict


def _prescreen_verdict(ssn, tasks, builder: "ScenarioBuilder", sp):
    """``_prefix_prescreen`` under its span ``sp``: the verdict, ``()``
    where the device was asked and gave none, or None where the batch was
    not asked, the reason on the span."""
    def declined(why: str) -> None:
        sp.set(declined=why)

    steps = builder._steps
    cap = ssn.config.scenario_prescreen_max
    if cap <= 0:
        return declined("disabled")
    if len(steps) < 3:
        return declined("few-steps")
    if any(t.is_fractional or t.resource_claims or t.res_req.mig_resources
           for t in tasks):
        return declined("host-state-task")
    # Fractional VICTIMS release whole devices when their sharing group
    # empties (node_info._sync_group_releasing) — more than their
    # request vector — so the vector model would undercount and
    # unsoundly skip feasible prefixes.
    if any(t.is_fractional for _v, vtasks in steps for t in vtasks):
        return declined("fractional-victim")
    if ssn.compute_state_mask(tasks) is not None:
        return declined("hard-mask")
    for fn in ssn.anti_domain_fns + ssn.affinity_domain_fns:
        if fn(tasks) is not None:
            return declined("domain-rows")

    from ..ops.scenario_batch import (batch_prefix_feasibility,
                                      dispatched_form)

    # The domains of the job's required topology level, where it has one
    # (None: the fleet-wide verdict, sound for every job).
    job = builder.scenario.pending_job
    domains = None
    if not any(ps.has_own_topology_constraint()
               for ps in job.pod_sets.values()):
        for fn in ssn.required_domain_fns:
            domains = fn(job)
            if domains is not None:
                break
    level, n_domains = "none", 0
    pool_nodes = ssn.node_idle.shape[0]
    if domains is not None and len(domains[1]) > 2 * pool_nodes:
        # Domains of very unequal sizes: their table would hold more
        # padding than fleet, a prefix each.
        return declined("ragged-domains")

    steps = steps[:cap]
    # Sparse victim-release rows; padding (step index == num_prefixes)
    # drops in the device-side scatter.  Pow2 buckets keep the jit cache
    # small across (prefixes, rows, tasks) shapes.
    rows_step, rows_node, rows_vec = [], [], []
    for k, (_victim, vtasks) in enumerate(steps):
        for t in vtasks:
            idx = ssn.node_index(t.node_name)
            if idx >= 0:
                rows_step.append(k)
                rows_node.append(idx)
                rows_vec.append(t.res_req.to_vec(mig_as_gpu=False))
    if not rows_vec:
        return declined("no-release-rows")
    num_prefixes = _next_pow2(len(steps))
    m_pad = _next_pow2(len(rows_vec))
    n_res = ssn.node_releasing.shape[1]
    release_step = np.full(m_pad, num_prefixes, np.int32)
    release_step[:len(rows_step)] = rows_step
    release_node = np.zeros(m_pad, np.int32)
    release_node[:len(rows_node)] = rows_node
    release_vec = np.zeros((m_pad, n_res))
    release_vec[:len(rows_vec)] = rows_vec

    # Padding rows form their own job 1 so they can never fail job 0's
    # gang (a zero-req row could still miss on pod room).
    rows = propose.task_operands(
        ssn, [(builder.scenario.pending_job, tasks)])
    if rows is None:
        return declined("no-task-rows")
    # A static mask (the state-dependent ones declined above) goes with
    # the rows, all-true for the padding tasks, as one more row set: the
    # kernel picks its form from the rows and the mask's by the same
    # predicates, whatever the strategies.
    mask = propose._pad_rows(ssn.compute_static_mask(tasks), rows.t_pad,
                             True)
    named = {} if mask is None else {"task_node_mask": mask}
    static = {}
    form, scan_steps = dispatched_form(
        rows.task_req, rows.task_job, rows.task_sel, rows.task_tol, mask)
    if domains is not None and domains[4] and form != "counted":
        # A preferred level's boosts move where a run lands, and the
        # domain form lands runs by the strategies' scores alone: a gang
        # of several runs that also prefers a level keeps the fleet-wide
        # verdict, sound for every gang (a counted gang has no order to
        # move, and stays exact in the domain form).
        domains = None
    if domains is not None:
        level, slot_node, domain_ok, n_domains, _preferred = domains
        pool_nodes = len(slot_node)
        named.update(slot_node=slot_node, domain_ok=domain_ok)
        static.update(num_domains=len(domain_ok))
    sp.set(prefixes=num_prefixes, steps=len(steps), rows=m_pad,
           t_pad=rows.t_pad, form=form,
           mask="none" if mask is None else "static",
           strategy=propose.strategy_name(ssn), level=level,
           domains=n_domains)
    if form != "counted":
        sp.set(runs=scan_steps)

    from ..utils.deviceguard import CycleDeadlineExceeded, DeviceGuardError
    METRICS.inc("device_kernel_calls")
    # Every dispatched prescreen, whatever its form.
    METRICS.inc("scenario_prescreen_calls_total")
    # The next families move by 0 where the form adds nothing, so that they
    # read 0 and are not absent: a process whose gangs are all of mixed
    # rows counts none, one whose gangs are all alike takes no step.
    METRICS.inc("scenario_prescreen_counted_total", int(form == "counted"))
    METRICS.inc("scenario_prescreen_scan_steps_total", scan_steps)
    METRICS.inc("scenario_prescreen_masked_total", int(mask is not None))
    METRICS.inc("scenario_prescreen_domain_calls_total",
                int(domains is not None))
    # The [K, N] cells of the pools the call builds (N the slots of the
    # domains' table in the domain form): what its bytes follow.
    METRICS.inc("scenario_prescreen_pool_cells_total",
                num_prefixes * pool_nodes)
    try:
        feasible = propose.run_on_nodes(
            ssn, batch_prefix_feasibility,
            (release_step, release_node, release_vec, rows.task_req,
             rows.task_job, rows.task_sel, rows.task_tol),
            label="scenario_prescreen",
            validate=lambda r: getattr(r, "shape", (0,))[-1] >= len(steps),
            # By name only where there is one: a call that names a None
            # is another program to jit than the one that leaves it out,
            # which is how the unmasked cells prime theirs.
            named=named or None,
            num_prefixes=num_prefixes, gpu_strategy=ssn.gpu_strategy,
            cpu_strategy=ssn.cpu_strategy, **static)
    except CycleDeadlineExceeded:
        raise
    except DeviceGuardError:
        # The prescreen is an optimization: a dead device (with the
        # fallback also unavailable) must not abort the whole solve —
        # the sequential simulation path still works.  Empty tuple, not
        # None: "attempted and unavailable", so the solve loop doesn't
        # re-pay the failed dispatch on every subsequent scenario (the
        # step-index lookup skips it naturally).
        sp.set(unavailable=True)
        return ()
    feasible = np.asarray(feasible)
    if domains is None:
        return feasible[:len(steps)]
    # [2,K]: some one domain seats the gang; the fleet as one domain
    # holds it.  What the second passes and the first refuses is what the
    # domain axis pruned.
    seated, fleet = feasible[0, :len(steps)], feasible[1, :len(steps)]
    pruned = int(np.count_nonzero(fleet & ~seated))
    sp.set(pruned=pruned)
    METRICS.inc("scenario_prescreen_domain_pruned_total", pruned)
    return seated


def _unevicted_tasks(scenario: Scenario, stmt) -> list:
    evicted = {op.task.uid for op in stmt.ops if op.kind == "evict"}
    out = []
    for _, vtasks in scenario.victims:
        out.extend(t for t in vtasks if t.uid not in evicted)
    return out


def _simulate_attempt(ssn, stmt, scenario: Scenario,
                      require_all_victims_replaced: bool,
                      try_replace_victims: bool) -> bool:
    """Try to place the pending job (and re-place victims) on top of the
    statement's accumulated evictions."""
    batched = (_batched_confirm(ssn, stmt, scenario, try_replace_victims)
               if ssn.config.batched_scenario_confirm else None)
    if batched is not None:
        ok, all_replaced = batched
        if not ok:
            return False
        if require_all_victims_replaced and not all_replaced:
            return False
        return True

    placed = attempt_to_allocate_job(ssn, scenario.pending_job,
                                     pipeline_only=True, stmt=stmt,
                                     commit=False)
    if not placed:
        return False

    all_replaced = True
    if try_replace_victims:
        placed_again = []
        for vjob, vtasks in scenario.victims:
            replaced = attempt_to_allocate_job(ssn, vjob, pipeline_only=True,
                                               stmt=stmt, commit=False)
            if replaced:
                placed_again.append((vjob, vtasks))
            else:
                all_replaced = False
        _attempt_the_rest(ssn, stmt, placed_again)
    else:
        all_replaced = False
    if require_all_victims_replaced and not all_replaced:
        return False
    return True


def _plain_chunk(ssn, job, pending: bool = False):
    """tasks_to_allocate when the job is expressible in one concatenated
    kernel call; None routes the scenario to the sequential path (the
    same state classes attempt_to_allocate_job handles host-side).  The
    ``pending`` job may carry a job-level topology constraint: its
    candidate domains become its chunk's node subset
    (``_batched_confirm``).  A victim with one, and any job with a
    podset's own, still go the sequential way."""
    if any(ps.has_own_topology_constraint()
           for ps in job.pod_sets.values()):
        propose.declined("confirm", "podset-topology")
        return None
    if not pending and (job.required_topology_level
                        or job.preferred_topology_level):
        propose.declined("confirm", "victim-topology")
        return None
    tasks = job.tasks_to_allocate(
        subgroup_order_fn=ssn.pod_set_order_key,
        task_order_fn=ssn.task_order_key, real_allocation=False)
    return None if _host_state(tasks) else tasks


def _host_state(tasks) -> bool:
    """Whether a task needs state the concatenated kernel call lacks."""
    return any(t.is_fractional or t.resource_claims
               or t.res_req.mig_resources or t.host_ports
               or t.needs_storage_scheduling() for t in tasks)


def _the_rest(ssn, vtasks) -> list:
    """The pods of ``vtasks`` (what a scenario evicted of one victim)
    that no placement of the statement has put back yet, in the tasks'
    order."""
    return sorted((t for t in vtasks if t.status == PodStatus.RELEASING),
                  key=ssn.task_order_key)


def _attempt_the_rest(ssn, stmt, placed_again) -> None:
    """The second pass of a scenario's re-placement, attempt by attempt.

    The first pass is upstream's: one ``attempt_to_allocate_job`` a
    victim, which places its NEXT CHUNK again (its gang chunk where the
    scenario left it below its minimum, one pod where it did not).  An
    elastic victim of a prefix that runs on past the pending job's need
    (a gang held to one rack, whose prefix runs through eight) then lost
    the rest of what the scenario took of it, whatever room there was.
    This pass goes over the victims whose first attempt succeeded
    (``placed_again``: ``(job, evicted tasks)``), in their order, and
    places the rest a pod an attempt, as the allocate action grows an
    elastic job, until one finds no room.  Where the first pass left no
    room, every cell's case but the rack-bound reclaimer's, it does
    nothing."""
    for vjob, vtasks in placed_again:
        for _ in range(len(vtasks)):
            if not _the_rest(ssn, vtasks) or not attempt_to_allocate_job(
                    ssn, vjob, pipeline_only=True, stmt=stmt, commit=False):
                break


def _place_the_rest(ssn, stmt, placed_again) -> None:
    """``_attempt_the_rest`` in ONE multi-job call: every pod left, a
    chunk of its own, a victim's chunks a chain (each tried only where
    the one before it succeeded, ``allocate_jobs_kernel(job_follows=)``),
    applied in order under the queue-capacity gate.  No call where
    nothing is left; the attempts where the call cannot be made."""
    chunks = [(vjob, [t]) for vjob, vtasks in placed_again
              for t in _the_rest(ssn, vtasks)]
    if not chunks:
        return
    proposals = None if _host_state(t for _j, ts in chunks for t in ts) \
        else ssn.propose_placements_multi(chunks, pipeline_only=True)
    if proposals is None:
        return _attempt_the_rest(ssn, stmt, placed_again)
    stopped = set()
    for (job, tasks), prop in zip(chunks, proposals):
        if job.uid in stopped:
            continue
        if not prop.success or not ssn.is_job_over_queue_capacity(
                job, tasks).schedulable:
            stopped.add(job.uid)
            continue
        stmt.apply_bulk((task, node, True)
                        for task, node, _p in prop.placements)


def _batched_confirm(ssn, stmt, scenario: Scenario,
                     try_replace_victims: bool):
    """Exact-confirm pass in ONE device call: pending job first, then
    victim re-placements, all through the multi-job kernel
    (solvers/by_pod_solver.go runs these as N sequential AllocateJob
    calls — the dominant per-scenario cost at contention).

    A pending job with a topology level goes in under a node subset, a
    candidate domain of ``subset_nodes`` on the statement's state, in the
    candidates' order as ``_place_gated_job`` tries them: the call is
    made again with the next candidate only where the gang did not fit
    the one before, and a victim may land anywhere either time.

    Returns (ok, all_replaced), or None to fall back to the sequential
    path when any involved job needs host-side state."""
    pending_job = scenario.pending_job
    pending_tasks = _plain_chunk(ssn, pending_job, pending=True)
    if pending_tasks is None or not pending_tasks:
        return None
    # Same admission gates attempt_to_allocate_job applies.
    if not ssn.is_job_over_queue_capacity(
            scenario.pending_job, pending_tasks).schedulable:
        return (False, False)
    if not ssn.check_pre_predicates(pending_tasks).schedulable:
        return (False, False)

    chunks = [(scenario.pending_job, pending_tasks)]
    skipped_victim = False
    if try_replace_victims:
        for vjob, _vtasks in scenario.victims:
            vtasks = _plain_chunk(ssn, vjob)
            if vtasks is None:
                return None  # host-state victim: sequential path
            if not vtasks:
                skipped_victim = True
                continue
            if not ssn.is_job_over_queue_capacity(
                    vjob, vtasks).schedulable \
                    or not ssn.check_pre_predicates(vtasks).schedulable:
                skipped_victim = True
                continue
            chunks.append((vjob, vtasks))

    for job, _tasks in chunks:
        ssn.pre_job_allocation(job)
    subsets = [None]
    if pending_job.required_topology_level \
            or pending_job.preferred_topology_level:
        subsets = ssn.subset_nodes(pending_job, pending_tasks)
    for subset in subsets:
        proposals = ssn.propose_placements_multi(
            chunks, pipeline_only=True, node_subset=subset)
        if proposals is None:
            return None
        if proposals[0].success:
            break
    else:
        # No candidate domain, or the gang fits none of them.
        return (False, False)
    # Apply job by job, re-checking the queue-capacity gate against the
    # statement state accumulated so far — the kernel models NODE
    # capacity only, and two jobs that each fit a queue's quota alone
    # can exceed it together (sequential semantics: a victim whose gate
    # fails after earlier placements simply stays evicted).  Dropping a
    # gated-out job only frees node capacity the kernel had charged, so
    # the retained placements remain feasible.
    stmt.apply_bulk((task, node, True)
                    for task, node, _p in proposals[0].placements)
    all_replaced = try_replace_victims and not skipped_victim
    evicted_of = {vjob.uid: vtasks for vjob, vtasks in scenario.victims}
    placed_again = []
    for (job, tasks), prop in zip(chunks[1:], proposals[1:]):
        if not prop.success:
            all_replaced = False
            continue
        if not ssn.is_job_over_queue_capacity(job, tasks).schedulable:
            all_replaced = False
            continue
        stmt.apply_bulk((task, node, True)
                        for task, node, _p in prop.placements)
        placed_again.append((job, evicted_of[job.uid]))
    # What else the scenario took of the victims that stand again.
    _place_the_rest(ssn, stmt, placed_again)
    return (True, all_replaced)
