"""Gang allocation kernel: the per-cycle hot loop as one jitted scan.

The reference allocates task-by-task, job-by-job, each placement mutating
node state before the next score, with checkpoint/rollback around each gang
(pkg/scheduler/actions/common/allocate.go:20-163,
framework/statement.go:44-61).  This kernel reproduces those semantics
exactly as a ``lax.scan`` over the flattened task sequence:

- carry = (idle, releasing, pod_room, per-job checkpoint of each, current
  job id, current job ok-flag);
- a job boundary commits (keeps) or rolls back (restores checkpoint) the
  previous gang, mirroring Statement.Checkpoint/Rollback;
- each step evaluates THIS task's predicate row and score row against the
  *current* mutated state — the same greedy sequence the Go code walks, but
  with the node loop fully vectorized on the MXU-friendly [N,R] tensors;
- a task that fits nowhere fails its whole gang: remaining tasks are
  skipped and the gang's placements are discarded (gang all-or-nothing).

Tasks must arrive grouped by job (non-decreasing ``task_job``), ordered by
the host-side job/task ordering plugins — order is policy, placement is
mechanism; only the mechanism runs on device.

Pipelining: a task that fits only on idle+releasing resources claims the
releasing pool (status Pipelined host-side); allocated tasks claim idle.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .predicates import feasibility_row
from .scoring import BINPACK, score_row

EPS = 1e-9
NEG = -1e18


class AllocationResult(NamedTuple):
    placements: jnp.ndarray    # [T] int32 node index, -1 = unplaced
    pipelined: jnp.ndarray     # [T] bool, True = placed onto releasing pool
    job_success: jnp.ndarray   # [J] bool — gang fully placed
    node_idle: jnp.ndarray     # [N,R] post-allocation idle
    node_releasing: jnp.ndarray  # [N,R] post-allocation releasing pool
    # [T + T + J] int32: placements ++ pipelined ++ job_success fused on
    # device, so a caller needing all three pays ONE device->host
    # transfer (each one a synchronisation point) instead of three.  None
    # when the producing kernel doesn't fuse it.
    packed: "jnp.ndarray | None" = None


@functools.partial(jax.jit,
                   static_argnames=("gpu_strategy", "cpu_strategy",
                                    "allow_pipeline", "pipeline_only"))
def allocate_jobs_kernel(node_allocatable, node_idle, node_releasing,
                         node_labels, node_taints, node_pod_room,
                         task_req, task_job, task_selector, task_tolerations,
                         job_allowed, task_extra_scores=None,
                         task_node_mask=None, task_anti_domain=None,
                         task_aff_domain=None, job_extra_scores=None,
                         job_node_mask=None, first_job_node_mask=None,
                         job_follows=None,
                         gpu_strategy: int = BINPACK,
                         cpu_strategy: int = BINPACK,
                         allow_pipeline: bool = True,
                         pipeline_only: bool = False) -> AllocationResult:
    """Place every job's gang greedily; roll failed gangs back.

    job_allowed: [J] bool gate (e.g. queue capacity check, proportion
    capacity_policy) — a gated-out job fails without touching state.
    task_extra_scores: optional [T,N] additive score terms whose rows
    differ by task (nominated node, preferred affinity).
    task_node_mask: optional [T,N] bool hard predicate (inter-pod affinity
    terms against existing pods, upstream-predicate verdicts): a False
    node is infeasible for that task, not merely low-scored.
    job_extra_scores, job_node_mask: optional [J,N] rows that hold for
    every task of a job (the topology plugin's preferred-level boosts,
    the chosen domain's node subset): step ``t`` reads row
    ``task_job[t]``, added to / ANDed with the per-task row where both
    are given.  A padding job's row is read and never used: its job is
    gated out.
    first_job_node_mask: optional [N] bool row that holds for the tasks
    of job 0 alone (a scenario confirm's pending job held to its topology
    domain while the victims behind it go anywhere): one row where
    ``job_node_mask`` would be J of which J - 1 say nothing.
    job_follows: optional [J] bool, true on a job that is the next chunk
    of the job before it (a victim a scenario places again, its gang
    chunk and then its surplus a pod a chunk): it is tried only where
    that one succeeded, as a sequence of attempts stops at the first that
    fails, so a chunk that would never be applied takes no room from the
    jobs behind it.
    task_anti_domain: optional (dom [T,N] int32, marks [T] bool,
    avoids [T] bool) — in-gang REQUIRED anti-affinity for ONE term.
    ``dom`` maps nodes to the term's topology domains (-1 = no domain);
    a task with ``marks`` creates a pod matching the term's selector, a
    task with ``avoids`` carries the term.  Within a gang, K8s semantics
    (incl. symmetry) reduce to: an avoider cannot enter a domain where a
    marker already landed, and a marker cannot enter a domain where an
    avoider already landed.  Blocked state lives in the scan carry and
    resets at each job boundary, so rollback is automatic.
    task_aff_domain: optional (dom [T,N] int32, marks [T] bool,
    avoids [T] bool, static_ok [T,N] bool, bootstrap [T] bool) — in-gang
    REQUIRED affinity for ONE term.  An avoider may sit only in a domain
    holding a matching pod: one that held a match before the cycle
    (``static_ok``) OR one a gang marker landed in this scan
    (accumulated union).  ``bootstrap`` flags the upstream first-pod rule:
    a self-matching avoider may open a fresh domain while the gang has
    placed no marker yet.
    pipeline_only: scenario-simulation mode — all placements pipeline
    (statement.go ConvertAllAllocatedToPipelined semantics come free:
    nothing claims idle).

    An optional operand that is None is left out of the step when it is
    traced; no [T,N] constant stands in for it.
    """
    T = task_req.shape[0]
    N = node_allocatable.shape[0]
    if task_anti_domain is not None:
        anti_dom, anti_marks, anti_avoids = task_anti_domain
    if task_aff_domain is not None:
        aff_dom, aff_marks, aff_avoids, aff_static, aff_boot = \
            task_aff_domain

    class Carry(NamedTuple):
        idle: jnp.ndarray
        rel: jnp.ndarray
        room: jnp.ndarray
        ck_idle: jnp.ndarray
        ck_rel: jnp.ndarray
        ck_room: jnp.ndarray
        cur_job: jnp.ndarray
        cur_ok: jnp.ndarray
        # Self-anti-affinity: domains closed to avoiders (a marker landed)
        # and to markers (an avoider landed; upstream symmetry).
        blocked_avoiders: jnp.ndarray  # [N] bool
        blocked_markers: jnp.ndarray   # [N] bool
        # Self-affinity: union of domains gang markers landed in, and
        # whether any marker has landed yet (bootstrap gate).
        aff_union: jnp.ndarray         # [N] bool
        any_marker: jnp.ndarray        # scalar bool

    init = Carry(node_idle, node_releasing, node_pod_room,
                 node_idle, node_releasing, node_pod_room,
                 jnp.array(-1, jnp.int32), jnp.array(False),
                 jnp.zeros(N, bool), jnp.zeros(N, bool),
                 jnp.zeros(N, bool), jnp.array(False))

    def step(carry: Carry, t):
        j = task_job[t]
        new_job = j != carry.cur_job
        # Job boundary: commit previous gang if it succeeded, else restore.
        keep = jnp.where(new_job & ~carry.cur_ok, False, True)
        idle = jnp.where(keep, carry.idle, carry.ck_idle)
        rel = jnp.where(keep, carry.rel, carry.ck_rel)
        room = jnp.where(keep, carry.room, carry.ck_room)
        ck_idle = jnp.where(new_job, idle, carry.ck_idle)
        ck_rel = jnp.where(new_job, rel, carry.ck_rel)
        ck_room = jnp.where(new_job, room, carry.ck_room)
        ok = jnp.where(new_job, job_allowed[j], carry.cur_ok)
        if job_follows is not None:
            # At a boundary ``carry.cur_ok`` is the verdict on the job
            # before, whole.
            ok = jnp.where(new_job & job_follows[j], ok & carry.cur_ok, ok)
        blocked_avoiders = jnp.where(new_job, False, carry.blocked_avoiders)
        blocked_markers = jnp.where(new_job, False, carry.blocked_markers)
        aff_union = jnp.where(new_job, False, carry.aff_union)
        any_marker = jnp.where(new_job, False, carry.any_marker)

        req = task_req[t]
        fit_now, fit_future = feasibility_row(
            idle, rel, node_labels, node_taints, room, req,
            task_selector[t], task_tolerations[t])
        if pipeline_only:
            fit_now = jnp.zeros_like(fit_now)
        feasible = fit_now | (fit_future if (allow_pipeline or pipeline_only)
                              else jnp.zeros_like(fit_future))
        if task_node_mask is not None:
            feasible = feasible & task_node_mask[t]
        if job_node_mask is not None:
            feasible = feasible & job_node_mask[j]
        if first_job_node_mask is not None:
            feasible = feasible & (first_job_node_mask | (j != 0))
        if task_anti_domain is not None:
            feasible = feasible \
                & ~(anti_avoids[t] & blocked_avoiders) \
                & ~(anti_marks[t] & blocked_markers)
        if task_aff_domain is not None:
            # Required affinity: an avoider needs a matching pod in its
            # domain — pre-existing (static), placed by this gang (union),
            # or itself under the first-pod bootstrap rule.
            aff_ok = aff_static[t] | aff_union \
                | (aff_boot[t] & ~any_marker)
            feasible = feasible & jnp.where(aff_avoids[t], aff_ok, True)
        score = score_row(node_allocatable, idle, req, feasible,
                          fit_now, gpu_strategy, cpu_strategy)
        if task_extra_scores is not None:
            score = score + task_extra_scores[t]
        if job_extra_scores is not None:
            score = score + job_extra_scores[j]
        found = ok & jnp.any(feasible)
        best = jnp.argmax(jnp.where(feasible, score, NEG))
        pipelined = found & ~fit_now[best]

        one_hot = (jnp.arange(idle.shape[0]) == best) & found
        take_idle = jnp.where((one_hot & ~pipelined)[:, None], req[None, :],
                              0.0)
        take_rel = jnp.where((one_hot & pipelined)[:, None], req[None, :],
                             0.0)
        idle = idle - take_idle
        rel = rel - take_rel
        room = room - one_hot.astype(room.dtype)

        if task_anti_domain is not None:
            # Self-anti-affinity: close the winning node's whole topology
            # domain to the complementary role for the rest of the gang.
            dom_row = anti_dom[t]
            won_dom = dom_row[best]
            in_dom = found & (won_dom >= 0) & (dom_row == won_dom)
            blocked_avoiders = blocked_avoiders | (anti_marks[t] & in_dom)
            blocked_markers = blocked_markers | (anti_avoids[t] & in_dom)

        if task_aff_domain is not None:
            a_row = aff_dom[t]
            a_won = a_row[best]
            a_in_dom = found & (a_won >= 0) & (a_row == a_won)
            aff_union = aff_union | (aff_marks[t] & a_in_dom)
            any_marker = any_marker | (aff_marks[t] & found)

        ok = ok & found
        out = (jnp.where(found, best, -1).astype(jnp.int32), pipelined, found)
        return Carry(idle, rel, room, ck_idle, ck_rel, ck_room,
                     j.astype(jnp.int32), ok,
                     blocked_avoiders, blocked_markers,
                     aff_union, any_marker), out

    carry, (placements, pipelined, found) = jax.lax.scan(
        step, init, jnp.arange(T))

    # Final gang commits or rolls back too.
    idle = jnp.where(carry.cur_ok, carry.idle, carry.ck_idle)
    rel = jnp.where(carry.cur_ok, carry.rel, carry.ck_rel)

    num_jobs = job_allowed.shape[0]
    placed_per_job = jax.ops.segment_sum(found.astype(jnp.int32), task_job,
                                         num_segments=num_jobs)
    tasks_per_job = jax.ops.segment_sum(jnp.ones(T, jnp.int32), task_job,
                                        num_segments=num_jobs)
    job_success = (tasks_per_job > 0) & (placed_per_job == tasks_per_job)
    # Failed gangs contribute no placements.
    valid = job_success[task_job]
    placements = jnp.where(valid, placements, -1)
    pipelined = pipelined & valid
    packed = jnp.concatenate([placements,
                              pipelined.astype(jnp.int32),
                              job_success.astype(jnp.int32)])
    return AllocationResult(placements, pipelined, job_success, idle, rel,
                            packed)
