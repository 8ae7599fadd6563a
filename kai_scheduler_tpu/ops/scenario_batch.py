"""Batched scenario feasibility: score K victim prefixes in one call.

The scenario solvers (actions/solvers.py, mirroring
pkg/scheduler/actions/common/solvers/job_solver.go:47-90) accumulate
victims one step at a time and simulate each prefix — one dispatch and
one fetch per scenario, with the device idle while the host prepares the
next, so worst-case reclaim latency is scenario-count-bound (SURVEY §7.6
/ BASELINE config #3 call this out).

This kernel evaluates ALL prefixes at once: prefix k's node state is the
live state plus the cumulative released resources of victims 1..k (an
eviction moves a victim's request into the releasing pool).  The result
is a [K] feasibility vector from ONE device call; the solver then
exact-confirms only the smallest feasible prefix through the ordinary
statement path (validators, victim re-placement, masks), so semantics
stay identical to the sequential search.

A prefix is answered in one of two forms, chosen in the program from
what the task rows hold (``uniform_gang``, ``continues_run``), never by a
flag.  Two rows are the same pod when request, selector, tolerations and,
where the call carries a static ``task_node_mask`` (a required node
affinity: node labels and names alone, which no eviction changes), their
``[N]`` mask rows agree: the mask row is part of a run's identity, and
ANDs into the run's hard feasibility.

* **counted** — the gang's real rows (job 0) are all one pod.  A
  pipeline-only placement takes ``req`` from the chosen node's releasing
  pool and one pod of its room and nothing anywhere else, so node n
  takes exactly ``c_n`` pods whatever the order or the score, and the
  gang fits iff the hard-feasible nodes' ``c_n`` sum to its size.  One
  elementwise pass over the pools and one reduction over N.
* **grouped** — a gang of several runs of identical adjacent rows (a
  master beside its workers), under either strategy on either axis: one
  dependent step a RUN, for all prefixes at once.  A count is exact for
  one run only, because a second run's fit depends on where the first
  landed; so between two runs the first run's pods are taken from the
  carried pool and the pod room where the exact kernel would have put
  them.  Nothing claims idle in a pipeline-only attempt (the exact
  kernel's step zeroes ``fit_now``, so a placement takes ``req`` from the
  releasing pool and one pod of the room), and the strategy's score
  reads idle alone: spread's ``idle / allocatable`` is the same row at
  every step of a run, bin-pack's is monotone in the same idle whatever
  the min/max span does.  So under EITHER strategy the exact kernel's
  ``argmax`` picks the best-scoring feasible node and keeps picking it
  until its capacity is spent, then the next: "sort by initial score,
  fill in order", the grouped kernel's fill plan (``allocate_grouped``,
  a threshold select with no sort) keyed by the score of the strategies
  the call was compiled for, with the count's capacity.  The argument
  asks of a run only that its pods share one feasible set, not which
  nodes are in it: a mask row that every pod of the run carries narrows
  the set and the run lands as before.  Spread round-robins where a
  placement claims idle (the bind, the grouped fill of
  ``framework/propose.py``): not here.  Only the releasing pool and the
  room are carried.  The last run needs no landing: it is a count.

Rows that differ open a new run, whichever of the four differs, and a run
of ONE pod landed by ``land`` is the exact kernel's step (the best-scoring
feasible node takes the pod): a gang whose every pod carries another mask
row is ``t`` runs of one, the pod-by-pod scan in this form.  So no third
form is needed; the exact kernel vmapped over the prefixes
(``allocate_jobs_kernel``, one dependent step a POD) is the oracle the
tests hold both forms to (``tests/prescreen_oracle.py``).

A gang with a REQUIRED topology level is answered domain by domain
(``domain_verdicts``): the same two forms vmapped over the level's
domains, each a small fleet, under the rule the host's ``subset_nodes``
applies to one state (``ops/topology.py`` ``domain_holds``); a prefix
passes where some one domain seats the gang.  Where the job also PREFERS
a level, its boosts change where a run lands and this module does not
model them: a counted gang stays exact, because a count has no order,
and a gang of several runs is left to the fleet-wide verdict by the
caller (``actions/solvers.py``), which is sound for every gang.

Both read the same dense per-prefix pools (scatter-add of the release
rows, running sum over the prefix axis).  The counted form could be had
from the touched nodes alone without ever materialising them; the pools
stay dense because the benchmark's byte count for this program
(``prefix_feasibility_bytes``: one [K,N,R] f32 pool written, and read
once a run) is what its roofline share is measured against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .allocate import NEG
from .allocate_grouped import _fill_by_score_descent, _score_keys
from .predicates import EPS, hard_row
from .scoring import BINPACK, score_row, score_row_selected


def _row_sets(task_req, task_selector, task_tolerations, task_node_mask):
    """The row sets that make two task rows one pod; the mask's only where
    the call has one (a trace-time fact: an unmasked call compares what
    it always did)."""
    rows = (task_req, task_selector, task_tolerations)
    return rows if task_node_mask is None else (*rows, task_node_mask)


def uniform_gang(task_req, task_job, task_selector, task_tolerations,
                 task_node_mask=None):
    """Scalar bool: is every row of job 0 the same pod as row 0?  The
    predicate behind the counted form: the device evaluates it on its
    operands, the host (numpy rows) on what it sends, to label the call."""
    real = task_job == 0
    same = real
    for rows in _row_sets(task_req, task_selector, task_tolerations,
                          task_node_mask):
        same = same & (rows == rows[0]).all(axis=-1)
    return (same == real).all()


def continues_run(task_req, task_job, task_selector, task_tolerations,
                  task_node_mask=None):
    """[T-1] bool: is row t+1 the pod of row t, both of job 0?  The
    predicate behind the grouped form's runs, for jax and numpy rows
    alike, as ``uniform_gang`` is."""
    real = task_job == 0
    same = real[1:] & real[:-1]
    for rows in _row_sets(task_req, task_selector, task_tolerations,
                          task_node_mask):
        same = same & (rows[1:] == rows[:-1]).all(axis=-1)
    return same


def gang_runs(task_req, task_job, task_selector, task_tolerations,
              task_node_mask=None):
    """Scalar: the runs of identical adjacent rows job 0 is made of."""
    # Every real row opens a run or continues one.
    return (task_job == 0).sum() - continues_run(
        task_req, task_job, task_selector, task_tolerations,
        task_node_mask).sum()


def dispatched_form(task_req, task_job, task_selector, task_tolerations,
                    task_node_mask=None):
    """(form, dependent steps over the pools) of the call that
    ``batch_prefix_feasibility`` makes of these rows: ``counted`` 0,
    ``grouped`` its runs, with a mask as without.  The host's reading of
    what it sends, by the predicates the program applies; the strategies
    choose the run's key, never the form."""
    rows = (task_req, task_job, task_selector, task_tolerations,
            task_node_mask)
    if bool(uniform_gang(*rows)):
        return "counted", 0
    return "grouped", int(gang_runs(*rows))


def corrected_count(quotient, req, total):
    """The largest whole ``c >= 0`` with ``c * req <= total + EPS``, given
    ``quotient`` within one of it either way.

    TPU f32 division is not correctly rounded: ``floor(total / req)``
    comes out one low on exact-integer quotients (ROADMAP D12), and one
    low here would call a feasible prefix infeasible.  So the count is
    settled by the scan's own comparison on exact products, once each
    way."""
    c = quotient
    c = c + ((c + 1.0) * req <= total + EPS)
    c = c - (c * req > total + EPS)
    return jnp.maximum(c, 0.0)


def stack_count(totals, room, req):
    """The whole pods of request ``req`` [R] that stack into each node:
    ``totals`` one array of free amounts a resource, ``room`` the pod
    room, of any shapes that broadcast.  The one count of "whole pods
    stacked node by node, pod room" of the prescreen's forms: a run's
    capacity, and a node's accommodation under ``domain_holds``."""
    count = jnp.floor(room)
    # R unrolled, a plane a resource (feasibility_caps_row's way): the
    # compiler keeps N minor in the pool, and 3 would waste the lanes.
    for res, total in enumerate(totals):
        rq = req[res]
        safe = jnp.where(rq > 0, rq, 1.0)
        fits = corrected_count(jnp.floor(total / safe), safe, total)
        count = jnp.where(rq > 0, jnp.minimum(count, fits), count)
    return count


def run_capacity(rel_planes, node_idle, hard, room, req):
    """[K,N]: the pods of request ``req`` that each node takes under each
    prefix's pool, ``rel_planes`` a [K,N] plane a resource; ``hard`` [N]
    and ``room`` [N] or [K,N] bound it."""
    totals = tuple(node_idle[None, :, res] + plane
                   for res, plane in enumerate(rel_planes))
    return jnp.where(hard, stack_count(totals, room, req), 0.0)


def _seats(capacity, need):
    """[K] bool: do the nodes' capacities hold ``need`` pods?  No node
    takes more than the run, so the sum stays small and exact."""
    return jnp.sum(jnp.minimum(capacity, need), axis=1) >= need


def _planes(prefix_rel):
    return tuple(prefix_rel[:, :, res] for res in range(prefix_rel.shape[2]))


def count_prefixes(prefix_rel, node_idle, node_labels, node_taints,
                   node_room, task_req, task_job, task_selector,
                   task_tolerations, task_node_mask=None):
    """The counted form: [K] bool from ``prefix_rel`` [K,N,R] for a gang
    of ``sum(task_job == 0)`` pods, each the pod of row 0."""
    need = jnp.sum(task_job == 0).astype(node_idle.dtype)
    # Prefix-invariant, [N]: evicted pods stay on their node as Releasing.
    hard = hard_row(node_labels, node_taints, node_room, task_selector[0],
                    task_tolerations[0])
    if task_node_mask is not None:
        hard = hard & task_node_mask[0]
    capacity = run_capacity(_planes(prefix_rel), node_idle, hard,
                            node_room, task_req[0])
    return (need > 0) & _seats(capacity, need)


def group_prefixes(prefix_rel, node_allocatable, node_idle, node_labels,
                   node_taints, node_room, task_req, task_job,
                   task_selector, task_tolerations, task_node_mask=None,
                   gpu_strategy: int = BINPACK, cpu_strategy: int = BINPACK,
                   f32_keys: bool = False):
    """The grouped form: [K] bool from ``prefix_rel`` [K,N,R] for a gang
    of runs of identical rows, each run landed by the key of the (static)
    strategies among the nodes its mask row admits.  A ``while`` over the
    runs, outside the prefix axis; ``f32_keys`` orders scores at the
    chip's precision on any backend (``_score_keys``)."""
    k, n, _ = prefix_rel.shape
    t = task_req.shape[0]
    dtype = node_idle.dtype
    real = task_job == 0
    opens = jnp.concatenate([real[:1], real[1:] & ~continues_run(
        task_req, task_job, task_selector, task_tolerations,
        task_node_mask)])
    runs = opens.sum()
    first_row = jnp.nonzero(opens, size=t, fill_value=0)[0]
    run_size = jax.ops.segment_sum(real.astype(dtype),
                                   jnp.cumsum(opens) - 1, num_segments=t)
    never_now = jnp.zeros(n, bool)    # pipeline-only: nothing claims idle
    # One scored column where the axes agree, both where they differ.
    score_of = score_row_selected if gpu_strategy == cpu_strategy \
        else score_row

    def capacity_of(run, rel, room):
        row = first_row[run]
        # The carried room never exceeds node_room: the static row's own
        # room test is implied by the capacity's.
        hard = hard_row(node_labels, node_taints, node_room,
                        task_selector[row], task_tolerations[row])
        if task_node_mask is not None:
            hard = hard & task_node_mask[row]
        return run_capacity(rel, node_idle, hard, room, task_req[row])

    def land(state):
        """Count run ``i`` and take its pods where the exact kernel puts
        them: descending score, ascending index among ties, each node to
        its capacity."""
        i, ok, rel, room = state
        req, need = task_req[first_row[i]], run_size[i]
        capacity = capacity_of(i, rel, room)
        feasible = capacity >= 1.0
        score = jax.vmap(
            lambda fits: score_of(
                node_allocatable, node_idle, req, fits, never_now,
                gpu_strategy, cpu_strategy))(feasible)
        key, levels, utype = _score_keys(jnp.where(feasible, score, NEG),
                                         f32_keys)
        take = jax.vmap(
            lambda keys, caps: _fill_by_score_descent(
                keys, levels, utype, caps, need))(
            key, jnp.minimum(capacity, need))
        rel = tuple(plane - take * req[res]
                    for res, plane in enumerate(rel))
        return i + 1, ok & _seats(capacity, need), rel, room - take

    init = (jnp.zeros((), runs.dtype), jnp.ones(k, bool),
            _planes(prefix_rel),
            jnp.broadcast_to(node_room.astype(dtype), (k, n)))
    # The last run lands nowhere that matters: it is counted.
    last, ok, rel, room = jax.lax.while_loop(
        lambda state: state[0] < runs - 1, land, init)
    return (runs > 0) & ok & _seats(capacity_of(last, rel, room),
                                    run_size[last])


def domain_verdicts(node_allocatable, node_idle, node_releasing,
                    node_labels, node_taints, node_room, release_step,
                    release_node, release_vec, task_req, task_job,
                    task_selector, task_tolerations, task_node_mask, *,
                    slot_node, domain_ok, num_prefixes: int,
                    num_domains: int, gpu_strategy: int, cpu_strategy: int):
    """[2,K] bool for a gang with a REQUIRED topology level.

    The nodes come by domain: ``slot_node`` [D*S] int32 is the node of
    each slot of a ``[D,S]`` table, a domain a row in ascending node index
    (``ops/topology.py`` ``domain_slots``), ``N`` where the row is padding;
    ``domain_ok`` [D] bool the domains the job may use (all but where it
    is pinned to those that hold its running pods).  The pools are built
    over the slots, so a domain is a row of them and no segment op runs
    over ``[K,N]``; a release on a node of no domain drops out.

    Row 0: for some one domain, ``TopologySession.subset_nodes``' rule
    holds (``domain_holds`` of ``ops/topology.py`` on the domain's free
    sums and its nodes' ``stack_count``: the host's own two functions) AND
    the gang fits the domain's nodes by the form its rows choose: counted,
    or grouped (each run landed by score AMONG THE DOMAIN'S NODES, as the
    exact kernel does under the domain's node mask).  The forms are the
    fleet-wide ones,
    vmapped over the domain axis: a domain is a small fleet.  A padding
    slot has no pod room, a padding domain no slot with any, so neither
    holds a gang.  Ties in a run's landing go to the lower slot, which is
    the lower node index inside a domain.

    Row 1: the same rule read with the fleet as its one domain (the root
    level's): what a level-blind capacity check passes.  A prefix true
    there and false in row 0 is one the domain axis pruned."""
    from .topology import domain_holds

    n, r = node_releasing.shape
    d = num_domains
    s = slot_node.shape[0] // d
    dtype = node_idle.dtype
    real = task_job == 0

    def by_domain(rows, fill):
        out = jnp.take(rows, slot_node, axis=0, mode="fill",
                       fill_value=fill)
        return out.reshape((d, s) + rows.shape[1:])

    allocatable = by_domain(node_allocatable, 0)
    idle = by_domain(node_idle, 0)
    labels = by_domain(node_labels, -1)
    taints = by_domain(node_taints, -1)
    room = by_domain(node_room, 0)
    node_slot = jnp.full(n, d * s, jnp.int32).at[slot_node].set(
        jnp.arange(d * s, dtype=jnp.int32), mode="drop")
    delta = jnp.zeros((num_prefixes, d * s, r), dtype)
    delta = delta.at[release_step, node_slot[release_node]].add(
        release_vec.astype(dtype), mode="drop")
    pools = (by_domain(node_releasing, 0).reshape(d * s, r)[None]
             + jnp.cumsum(delta, axis=0)).reshape(num_prefixes, d, s, r)
    mask = None if task_node_mask is None else jnp.take(
        task_node_mask, slot_node, axis=1, mode="fill",
        fill_value=False).reshape(-1, d, s)
    rows = (task_req, task_job, task_selector, task_tolerations)
    mask_axis = None if mask is None else 1

    def counted():
        return jax.vmap(count_prefixes,
                        in_axes=(1, 0, 0, 0, 0) + (None,) * 4
                        + (mask_axis,))(
            pools, idle, labels, taints, room, *rows, mask)

    def grouped():
        fn = functools.partial(group_prefixes, gpu_strategy=gpu_strategy,
                               cpu_strategy=cpu_strategy)
        return jax.vmap(fn, in_axes=(1, 0, 0, 0, 0, 0) + (None,) * 4
                        + (mask_axis,))(
            pools, allocatable, idle, labels, taints, room, *rows, mask)

    fits = jax.lax.cond(uniform_gang(*rows, task_node_mask), counted,
                        grouped)                                 # [D,K]

    gang = real.sum().astype(dtype)
    reqs = jnp.where(real[:, None], task_req, 0.0)
    totals = tuple(idle[None, :, :, res] + pools[..., res]
                   for res in range(r))                          # [K,D,S]
    stacked = jnp.clip(stack_count(totals, room[None], reqs.max(axis=0)),
                       0.0, gang)
    free = jnp.stack([t.sum(axis=-1) for t in totals], axis=-1)  # [K,D,R]
    pods = stacked.sum(axis=-1)                                   # [K,D]
    total_req = reqs.sum(axis=0)
    holds = domain_holds(free, pods, total_req, gang)
    seated = jnp.any(holds & fits.T & domain_ok[None, :], axis=1)
    fleet = domain_holds(free.sum(axis=1), pods.sum(axis=1), total_req,
                         gang)
    return jnp.stack([(gang > 0) & seated, (gang > 0) & fleet])


@functools.partial(jax.jit,
                   static_argnames=("num_prefixes", "gpu_strategy",
                                    "cpu_strategy", "num_domains"))
def batch_prefix_feasibility(node_allocatable, node_idle, node_releasing,
                             node_labels, node_taints, node_room,
                             release_step, release_node, release_vec,
                             task_req, task_job, task_selector,
                             task_tolerations, num_prefixes: int,
                             task_node_mask=None,
                             gpu_strategy: int = BINPACK,
                             cpu_strategy: int = BINPACK,
                             slot_node=None, domain_ok=None,
                             num_domains: int = 0) -> jnp.ndarray:
    """[num_prefixes] bool: can the pending job pipeline onto each
    prefix's released resources?

    Victim releases arrive SPARSE — (release_step [M], release_node [M],
    release_vec [M,R]) rows, padded with step >= num_prefixes — and the
    dense per-prefix releasing pools materialize on device (scatter-add +
    cumulative sum over the prefix axis), so the host->device transfer is
    O(victim tasks), never O(prefixes x nodes).  node_room is
    prefix-invariant (evicted pods stay on their node as Releasing).

    A gang of identical pods is counted and any other stepped over run
    by run, each run landed by the strategies' key (module docstring).  A
    ``task_node_mask`` [T,N] bool, all-true on the padding rows, is static
    (no eviction changes it): its rows tell pods apart as the other rows
    do, and a run's row bounds where the run may land.

    With ``slot_node`` the gang carries a REQUIRED topology level and the
    answer is [2, num_prefixes]: row 0, whether SOME ONE domain of the
    level seats the gang with the prefix released; row 1, whether the
    fleet as one domain holds it (``domain_verdicts``).
    """
    tasks = (task_req, task_job, task_selector, task_tolerations,
             task_node_mask)
    if slot_node is not None:
        return domain_verdicts(
            node_allocatable, node_idle, node_releasing, node_labels,
            node_taints, node_room, release_step, release_node,
            release_vec, *tasks, slot_node=slot_node, domain_ok=domain_ok,
            num_prefixes=num_prefixes, num_domains=num_domains,
            gpu_strategy=gpu_strategy, cpu_strategy=cpu_strategy)

    def pools():
        n, r = node_releasing.shape
        delta = jnp.zeros((num_prefixes, n, r), node_releasing.dtype)
        delta = delta.at[release_step, release_node].add(release_vec,
                                                         mode="drop")
        return node_releasing[None, :, :] + jnp.cumsum(delta, axis=0)

    def counted():
        return count_prefixes(pools(), node_idle, node_labels,
                              node_taints, node_room, *tasks)

    def grouped():
        return group_prefixes(pools(), node_allocatable, node_idle,
                              node_labels, node_taints, node_room, *tasks,
                              gpu_strategy=gpu_strategy,
                              cpu_strategy=cpu_strategy)

    # At the top level, outside any vmap, where a cond would turn into a
    # select and run both.
    return jax.lax.cond(uniform_gang(*tasks), counted, grouped)
