"""Multi-chip gang allocation: the cycle kernel under shard_map.

The node axis of the packed snapshot shards across chips; every per-task
step reduces its candidate scores with ICI collectives (pmin/pmax for the
global bin-pack scale, all_gather for the global argmax) and only the chip
owning the winning node mutates its shard.  This is the scaling design of
SURVEY.md §2.6.5: one SPMD program per cycle instead of the reference's
goroutine fan-out, with the SchedulingShard partition folded into the mesh.

Determinism matches the single-chip kernel exactly: the gathered
(score, node-index) pairs are reduced first-max-wins, which equals the
lowest-global-index tie-break of ops/allocate.allocate_jobs_kernel.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.allocate import NEG, AllocationResult
from ..ops.predicates import feasibility_row
from ..ops.scoring import BINPACK, score_row
from .mesh import NODE_AXIS


def _global_minmax(free_local, valid_local, axis_name):
    """[Nl,R] free, [Nl] valid -> replicated [2,R] (min, max) over the
    mesh: the bin-pack scale must be identical on every shard."""
    big = jnp.inf
    mn = jnp.min(jnp.where(valid_local[:, None], free_local, big), axis=0)
    mx = jnp.max(jnp.where(valid_local[:, None], free_local, -big), axis=0)
    mn = jax.lax.pmin(mn, axis_name)
    mx = jax.lax.pmax(mx, axis_name)
    return jnp.stack([mn, mx])


@functools.partial(jax.jit,
                   static_argnames=("mesh", "gpu_strategy", "cpu_strategy",
                                    "allow_pipeline"))
def sharded_allocate_jobs(mesh, node_allocatable, node_idle, node_releasing,
                          node_labels, node_taints, node_pod_room,
                          task_req, task_job, task_selector,
                          task_tolerations, job_allowed,
                          task_node_mask=None,
                          gpu_strategy: int = BINPACK,
                          cpu_strategy: int = BINPACK,
                          allow_pipeline: bool = True) -> AllocationResult:
    """Multi-chip version of ops.allocate.allocate_jobs_kernel.

    Node arrays shard over the mesh's ``nodes`` axis (their leading
    dimension must divide evenly); task/job arrays replicate.
    task_node_mask ([T,N] hard feasibility, e.g. inter-pod affinity)
    shards over its node axis.  Self-gang anti-affinity domain rows are
    not supported here — the action layer keeps such jobs on the
    single-chip kernel.
    """
    n = node_allocatable.shape[0]
    d = mesh.devices.size
    assert n % d == 0, f"node axis {n} must divide mesh size {d}"
    t = task_req.shape[0]
    if task_node_mask is None:
        task_node_mask = jnp.ones((t, n), bool)

    node_spec = P(NODE_AXIS)
    rep = P()

    @functools.partial(
        jax.shard_map, mesh=mesh, check_vma=False,
        in_specs=(node_spec, node_spec, node_spec, node_spec, node_spec,
                  node_spec, rep, rep, rep, rep, rep, P(None, NODE_AXIS)),
        out_specs=(rep, rep, rep, node_spec, node_spec))
    def run(alloc, idle, rel, labels, taints, room,
            treq, tjob, tsel, ttol, jallowed, tmask):
        n_local = alloc.shape[0]
        my_dev = jax.lax.axis_index(NODE_AXIS)
        offset = my_dev * n_local

        class Carry(NamedTuple):
            idle: jnp.ndarray
            rel: jnp.ndarray
            room: jnp.ndarray
            ck_idle: jnp.ndarray
            ck_rel: jnp.ndarray
            ck_room: jnp.ndarray
            cur_job: jnp.ndarray
            cur_ok: jnp.ndarray

        init = Carry(idle, rel, room, idle, rel, room,
                     jnp.array(-1, jnp.int32), jnp.array(False))

        def step(carry: Carry, ti):
            j = tjob[ti]
            new_job = j != carry.cur_job
            keep = jnp.where(new_job & ~carry.cur_ok, False, True)
            c_idle = jnp.where(keep, carry.idle, carry.ck_idle)
            c_rel = jnp.where(keep, carry.rel, carry.ck_rel)
            c_room = jnp.where(keep, carry.room, carry.ck_room)
            ck_idle = jnp.where(new_job, c_idle, carry.ck_idle)
            ck_rel = jnp.where(new_job, c_rel, carry.ck_rel)
            ck_room = jnp.where(new_job, c_room, carry.ck_room)
            ok = jnp.where(new_job, jallowed[j], carry.cur_ok)

            req = treq[ti]
            fit_now, fit_future = feasibility_row(
                c_idle, c_rel, labels, taints, c_room, req, tsel[ti],
                ttol[ti])
            feasible = fit_now | (fit_future if allow_pipeline
                                  else jnp.zeros_like(fit_future))
            feasible = feasible & tmask[ti]
            minmax = _global_minmax(c_idle, feasible, NODE_AXIS)
            score = score_row(alloc, c_idle, req, feasible, fit_now,
                              gpu_strategy, cpu_strategy, minmax=minmax)
            score = jnp.where(feasible, score, NEG)

            # Global argmax: gather each shard's champion; first max wins
            # (= lowest global node index among ties).
            local_best = jnp.argmax(score)
            local_score = score[local_best]
            scores_all = jax.lax.all_gather(local_score, NODE_AXIS)
            idx_all = jax.lax.all_gather(local_best + offset, NODE_AXIS)
            win_dev = jnp.argmax(scores_all)
            win_score = scores_all[win_dev]
            win_idx = idx_all[win_dev]
            found = ok & (win_score > NEG / 2)

            mine = win_dev == my_dev
            local_win = win_idx - offset
            one_hot = (jnp.arange(n_local) == local_win) & mine & found
            # Only the winning shard knows whether its node fits now; the
            # others contribute False so the OR-reduce carries the winner's
            # verdict to every shard.
            not_fit_now_here = mine & ~fit_now[
                jnp.clip(local_win, 0, n_local - 1)]
            pipelined = found & jax.lax.pmax(
                not_fit_now_here.astype(jnp.int32), NODE_AXIS).astype(bool)

            take_idle = jnp.where((one_hot & ~pipelined)[:, None],
                                  req[None, :], 0.0)
            take_rel = jnp.where((one_hot & pipelined)[:, None],
                                 req[None, :], 0.0)
            n_idle = c_idle - take_idle
            n_rel = c_rel - take_rel
            n_room = c_room - one_hot.astype(c_room.dtype)

            ok = ok & found
            out = (jnp.where(found, win_idx, -1).astype(jnp.int32),
                   pipelined, found)
            return Carry(n_idle, n_rel, n_room, ck_idle, ck_rel, ck_room,
                         j.astype(jnp.int32), ok), out

        carry, (placements, pipelined, found) = jax.lax.scan(
            step, init, jnp.arange(t))
        f_idle = jnp.where(carry.cur_ok, carry.idle, carry.ck_idle)
        f_rel = jnp.where(carry.cur_ok, carry.rel, carry.ck_rel)
        return placements, pipelined, found, f_idle, f_rel

    placements, pipelined, found, idle_out, rel_out = run(
        node_allocatable, node_idle, node_releasing, node_labels,
        node_taints, node_pod_room, task_req, task_job, task_selector,
        task_tolerations, job_allowed, task_node_mask)

    num_jobs = job_allowed.shape[0]
    placed = jax.ops.segment_sum(found.astype(jnp.int32), task_job,
                                 num_segments=num_jobs)
    total = jax.ops.segment_sum(jnp.ones(t, jnp.int32), task_job,
                                num_segments=num_jobs)
    job_success = (total > 0) & (placed == total)
    valid = job_success[task_job]
    placements = jnp.where(valid, placements, -1)
    pipelined = pipelined & valid
    packed = jnp.concatenate([placements,
                              pipelined.astype(jnp.int32),
                              job_success.astype(jnp.int32)])
    return AllocationResult(placements, pipelined, job_success, idle_out,
                            rel_out, packed)


def sharded_cycle_step(mesh, snapshot_arrays: dict, k_value: float = 1.0,
                       gpu_strategy: int = BINPACK,
                       cpu_strategy: int = BINPACK) -> dict:
    """One full scheduling step across the mesh: hierarchical fair share
    (replicated — the queue table is tiny), queue capacity gating, then the
    sharded gang allocation.  This is the "training step" analog the
    multi-chip dry-run compiles (SURVEY.md §7 minimum slice, distributed).
    """
    from ..ops.fairshare import LevelSpec, divide_groups_jax

    a = snapshot_arrays
    q = a["queue_deserved"].shape[0]
    spec = LevelSpec(num_groups=1, num_bands=int(a.get("num_bands", 1)))
    fair = divide_groups_jax(
        spec, a["total"][None, :], jnp.zeros(q, jnp.int32),
        a["queue_band"], a["queue_deserved"], a["queue_limit"],
        a["queue_over_quota_weight"], a["queue_request"], a["queue_usage"],
        a["queue_tiebreak"], k_value)

    # Queue gate: job's queue must stay within max(deserved, fair) + limit.
    job_q = a["job_queue"]
    job_req = jax.ops.segment_sum(a["task_req"], a["task_job"],
                                  num_segments=job_q.shape[0])
    allocatable = jnp.maximum(a["queue_deserved"], fair)
    allocatable = jnp.where(a["queue_limit"] < 0, allocatable,
                            jnp.minimum(a["queue_limit"], allocatable))
    headroom = allocatable - a["queue_allocated"]
    job_allowed = jnp.all(job_req <= headroom[job_q] + 1e-9, axis=-1)

    result = sharded_allocate_jobs(
        mesh, a["node_allocatable"], a["node_idle"], a["node_releasing"],
        a["node_labels"], a["node_taints"], a["node_pod_room"],
        a["task_req"], a["task_job"], a["task_selector"],
        a["task_tolerations"], job_allowed,
        gpu_strategy=gpu_strategy, cpu_strategy=cpu_strategy)
    return {"fair_share": fair, "job_allowed": job_allowed,
            "result": result}
