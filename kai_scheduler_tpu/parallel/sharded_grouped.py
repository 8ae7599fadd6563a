"""Multi-chip grouped gang allocation: fill plans over a sharded node axis.

Combines the two scaling ideas of this framework:
- ops/allocate_grouped.py: one analytic fill plan per run of identical
  tasks (scan length = number of groups);
- parallel/sharded.py: the node axis sharded across chips with ICI
  collectives replacing global reductions.

Per group, the fill threshold comes from the same sort-free radix
select as the single-chip kernel, with per-shard capacity histograms
psum-merged over ICI — every shard derives the identical replicated
threshold and computes its own local takes directly; threshold-equal
marginal nodes resolve in ascending GLOBAL index order through a
cross-shard exclusive prefix.  Only the compacted fill segments (at most
max_group per phase, gathered as [devices x K]) ever cross shards, so
the per-group communication cost is flat in cluster size.

Exactness matches allocate_grouped (and therefore the per-task kernel):
takes are integral and bounded by the gang size, so K = max_group
segment slots suffice per shard and globally.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.allocate import NEG, AllocationResult
from ..ops.allocate_grouped import _next_pow2, _score_keys, group_tasks
from ..ops.predicates import feasibility_row
from ..ops.scoring import BINPACK, score_row
from .mesh import NODE_AXIS
from .sharded import _global_minmax


def _fill_by_score_sharded(key, levels, utype, cap, count, axis_name):
    """Distributed exact greedy fill: radix-select the score threshold
    over psum-merged capacity histograms, then resolve the marginal
    (threshold-equal) nodes in ascending GLOBAL index order via an
    exclusive cross-shard prefix.  Returns this shard's local take [Nl].
    """
    n_bits = levels * 8
    ar = jnp.arange(256)
    prefix = jnp.zeros((), utype)
    above = jnp.zeros((), cap.dtype)
    for level in range(levels):
        shift = n_bits - 8 * (level + 1)
        digit = ((key >> utype(shift)) & utype(0xFF)).astype(jnp.int32)
        if level == 0:
            capw = cap
        else:
            in_prefix = (key >> utype(n_bits - 8 * level)) == prefix
            capw = jnp.where(in_prefix, cap, 0.0)
        onehot = (digit[:, None] == ar[None, :]).astype(cap.dtype)
        hist = jax.lax.psum(
            jnp.matmul(capw, onehot,
                       precision=jax.lax.Precision.HIGHEST), axis_name)
        ge = jnp.cumsum(hist[::-1])[::-1]
        gt = ge - hist
        need = count - above
        crossing = (gt < need) & (need <= ge)
        d_star = jnp.where(crossing.any(), jnp.argmax(crossing),
                           0).astype(jnp.int32)
        above = above + gt[d_star]
        prefix = (prefix << utype(8)) | d_star.astype(utype)
    take_full = jnp.where(key > prefix, cap, 0.0)
    eqcap = jnp.where(key == prefix, cap, 0.0)
    rem = jnp.maximum(count - above, 0.0)
    # Exclusive prefix of equal-key capacity across shards: lower global
    # indices (lower shard, then lower local index) fill first.
    local_sum = eqcap.sum()
    sums = jax.lax.all_gather(local_sum, axis_name)
    my_dev = jax.lax.axis_index(axis_name)
    shard_prefix = jnp.cumsum(sums)[my_dev] - local_sum
    pref = shard_prefix + jnp.cumsum(eqcap)
    take_eq = jnp.clip(rem - (pref - eqcap), 0.0, eqcap)
    return jnp.where(count > 0, take_full + take_eq, 0.0)


def _gather_segments(take, key, offset, max_group: int, axis_name):
    """Merge per-shard fill segments into the replicated global [K] lists
    ordered by descending score (ascending global index among ties)."""
    n_local = take.shape[0]
    flag = take > 0
    slot = jnp.cumsum(flag) - 1
    slot = jnp.where(flag, slot, max_group)
    l_nodes = jnp.full(max_group, -1, jnp.int32).at[slot].set(
        (jnp.arange(n_local, dtype=jnp.int32) + offset), mode="drop")
    l_counts = jnp.zeros(max_group, take.dtype).at[slot].set(
        take, mode="drop")
    l_keys = jnp.where(l_nodes >= 0,
                       key[jnp.clip(l_nodes - offset, 0, n_local - 1)],
                       jnp.zeros((), key.dtype))
    a_nodes = jax.lax.all_gather(l_nodes, axis_name).ravel()
    a_counts = jax.lax.all_gather(l_counts, axis_name).ravel()
    a_keys = jax.lax.all_gather(l_keys, axis_name).ravel()
    # Gathered order is (shard, local slot) = ascending global index; a
    # stable ascending argsort on the complemented key yields descending
    # score with that tie-break.  Empty slots (key 0 -> complement max)
    # sort last.  Only d*K elements — never the node axis.
    order = jnp.argsort(~a_keys, stable=True)[:max_group]
    return a_nodes[order], a_counts[order]


@functools.partial(jax.jit,
                   static_argnames=("mesh", "max_group", "gpu_strategy",
                                    "cpu_strategy", "allow_pipeline"))
def sharded_allocate_groups_kernel(mesh, node_allocatable, node_idle,
                                   node_releasing, node_labels, node_taints,
                                   node_pod_room, group_req, group_sel,
                                   group_tol, group_count, group_job,
                                   job_allowed, max_group: int,
                                   gpu_strategy: int = BINPACK,
                                   cpu_strategy: int = BINPACK,
                                   allow_pipeline: bool = True):
    """Returns (seg_nodes [G,K] global ids, seg_counts [G,K],
    seg_pipe [G,K], group_placed [G], job_success [J], idle', rel').

    Jitted with the mesh static: repeated rounds reuse the compiled
    executable instead of re-tracing the shard_map closure per call."""
    n = node_allocatable.shape[0]
    d = mesh.devices.size
    assert n % d == 0, f"node axis {n} must divide mesh size {d}"
    G = group_req.shape[0]
    K = max_group

    from jax.sharding import PartitionSpec as P
    node_spec = P(NODE_AXIS)
    rep = P()

    @functools.partial(
        jax.shard_map, mesh=mesh, check_vma=False,
        in_specs=(node_spec,) * 6 + (rep,) * 6,
        out_specs=(rep, rep, rep, rep, node_spec, node_spec))
    def run(alloc, idle, rel, labels, taints, room,
            g_req, g_sel, g_tol, g_count, g_job, j_allowed):
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

        def step(carry: Carry, g):
            j = g_job[g]
            new_job = j != carry.cur_job
            keep = jnp.where(new_job & ~carry.cur_ok, False, True)
            c_idle = jnp.where(keep, carry.idle, carry.ck_idle)
            c_rel = jnp.where(keep, carry.rel, carry.ck_rel)
            c_room = jnp.where(keep, carry.room, carry.ck_room)
            ck_idle = jnp.where(new_job, c_idle, carry.ck_idle)
            ck_rel = jnp.where(new_job, c_rel, carry.ck_rel)
            ck_room = jnp.where(new_job, c_room, carry.ck_room)
            ok = jnp.where(new_job, j_allowed[j], carry.cur_ok)

            req = g_req[g]
            count = jnp.where(ok, g_count[g], 0.0)

            fit_now, fit_future = feasibility_row(
                c_idle, c_rel, labels, taints, c_room, req, g_sel[g],
                g_tol[g])
            feasible = fit_now | (fit_future if allow_pipeline
                                  else jnp.zeros_like(fit_future))
            minmax = _global_minmax(c_idle, feasible, NODE_AXIS)
            score = score_row(alloc, c_idle, req, feasible, fit_now,
                              gpu_strategy, cpu_strategy, minmax=minmax)
            score = jnp.where(feasible, score, NEG)

            safe_req = jnp.where(req > 0, req, 1.0)
            cap_now_f = jnp.min(
                jnp.where(req[None, :] > 0,
                          jnp.floor(c_idle / safe_req[None, :]), jnp.inf),
                axis=1)
            cap_tot_f = jnp.min(
                jnp.where(req[None, :] > 0,
                          jnp.floor((c_idle + c_rel) / safe_req[None, :]),
                          jnp.inf), axis=1)
            cap_now = jnp.where(fit_now, jnp.minimum(cap_now_f, c_room),
                                0.0)
            cap_tot = jnp.where(feasible, jnp.minimum(cap_tot_f, c_room),
                                0.0)
            cap_now = jnp.clip(cap_now, 0.0, count)
            cap_tot = jnp.clip(cap_tot, 0.0, count)

            # Sort-free distributed fill: the score threshold comes from
            # radix-select over psum-merged capacity histograms (the
            # multi-chip form of the single-chip fill's threshold
            # search, ops/allocate_grouped._fill_by_score_descent),
            # replacing the per-step local+global top_k sorts.
            key, levels, utype = _score_keys(score)
            take_a = _fill_by_score_sharded(key, levels, utype, cap_now,
                                            count, NODE_AXIS)
            total_now = jax.lax.psum(take_a.sum(), NODE_AXIS)
            cap_b = cap_tot - take_a
            remaining = jnp.maximum(count - total_now, 0.0)
            take_b = _fill_by_score_sharded(key, levels, utype, cap_b,
                                            remaining, NODE_AXIS)
            if not allow_pipeline:
                take_b = jnp.zeros_like(take_b)
            placed = total_now + jax.lax.psum(take_b.sum(), NODE_AXIS)

            c_idle = c_idle - take_a[:, None] * req[None, :]
            c_rel = c_rel - take_b[:, None] * req[None, :]
            c_room = c_room - take_a - take_b

            # Segments: compact each shard's takes locally (ascending
            # local = ascending global index within the shard), gather all
            # shards' slots, and order the small [d*K] candidate list by
            # descending score with the ascending-global-index tie-break.
            seg_nodes_a, seg_take_a = _gather_segments(
                take_a, key, offset, K, NODE_AXIS)
            seg_nodes_b, seg_take_b = _gather_segments(
                take_b, key, offset, K, NODE_AXIS)

            ok = ok & (placed >= count)
            return (Carry(c_idle, c_rel, c_room, ck_idle, ck_rel, ck_room,
                          j.astype(jnp.int32), ok),
                    (seg_nodes_a, seg_take_a, seg_nodes_b, seg_take_b,
                     placed))

        carry, outs = jax.lax.scan(step, init, jnp.arange(G))
        seg_nodes_a, seg_take_a, seg_nodes_b, seg_take_b, placed = outs
        f_idle = jnp.where(carry.cur_ok, carry.idle, carry.ck_idle)
        f_rel = jnp.where(carry.cur_ok, carry.rel, carry.ck_rel)
        packed = jnp.concatenate([
            seg_nodes_a.astype(jnp.float32).ravel(),
            seg_take_a.astype(jnp.float32).ravel(),
            seg_nodes_b.astype(jnp.float32).ravel(),
            seg_take_b.astype(jnp.float32).ravel(),
        ])
        return packed, placed, jnp.zeros(()), jnp.zeros(()), f_idle, f_rel

    packed, group_placed, _, _, idle_out, rel_out = run(
        node_allocatable, node_idle, node_releasing, node_labels,
        node_taints, node_pod_room, group_req, group_sel, group_tol,
        group_count, group_job, job_allowed)

    num_jobs = job_allowed.shape[0]
    placed_per_job = jax.ops.segment_sum(group_placed, group_job,
                                         num_segments=num_jobs)
    count_per_job = jax.ops.segment_sum(group_count, group_job,
                                        num_segments=num_jobs)
    job_success = (count_per_job > 0) & (placed_per_job >= count_per_job) \
        & job_allowed
    return packed, group_placed, job_success, idle_out, rel_out


def sharded_allocate_grouped(mesh, node_arrays, task_req, task_job,
                             task_selector, task_tolerations, job_allowed,
                             gpu_strategy: int = BINPACK,
                             cpu_strategy: int = BINPACK,
                             allow_pipeline: bool = True
                             ) -> AllocationResult:
    """Host wrapper mirroring ops.allocate_grouped.allocate_grouped for a
    device mesh."""
    np_req = np.asarray(task_req)
    np_job = np.asarray(task_job)
    np_sel = np.asarray(task_selector)
    np_tol = np.asarray(task_tolerations)
    (group_of_task, g_req, g_sel, g_tol, g_count,
     g_job, _g_indep) = group_tasks(np_req, np_job, np_sel, np_tol)
    max_group = _next_pow2(int(g_count.max()) if len(g_count) else 1)

    packed, group_placed, job_success, idle, rel = \
        sharded_allocate_groups_kernel(
            mesh, *node_arrays, jnp.asarray(g_req), jnp.asarray(g_sel),
            jnp.asarray(g_tol), jnp.asarray(g_count), jnp.asarray(g_job),
            jnp.asarray(job_allowed), max_group=max_group,
            gpu_strategy=gpu_strategy, cpu_strategy=cpu_strategy,
            allow_pipeline=allow_pipeline)

    packed = np.asarray(packed)
    g, k = len(g_count), max_group
    seg_nodes_a = packed[:g * k].reshape(g, k).astype(np.int32)
    seg_take_a = packed[g * k:2 * g * k].reshape(g, k).astype(np.int64)
    seg_nodes_b = packed[2 * g * k:3 * g * k].reshape(g, k).astype(np.int32)
    seg_take_b = packed[3 * g * k:4 * g * k].reshape(g, k).astype(np.int64)
    success = np.asarray(job_success)

    T = np_req.shape[0]
    placements = np.full(T, -1, np.int32)
    pipelined = np.zeros(T, bool)
    t = 0
    for gi in range(g):
        count = int(g_count[gi])
        if success[g_job[gi]]:
            nodes = np.concatenate([
                np.repeat(seg_nodes_a[gi], seg_take_a[gi]),
                np.repeat(seg_nodes_b[gi], seg_take_b[gi])])
            pipes = np.concatenate([
                np.zeros(seg_take_a[gi].sum(), bool),
                np.ones(seg_take_b[gi].sum(), bool)])
            m = min(len(nodes), count)
            placements[t:t + m] = nodes[:m]
            pipelined[t:t + m] = pipes[:m]
        t += count
    # Host arrays throughout: consumers read them for free instead of
    # round-tripping a re-uploaded device array.
    return AllocationResult(placements, pipelined, success, idle, rel)
