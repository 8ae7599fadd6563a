"""Batched scenario feasibility: score K victim prefixes in one call.

The scenario solvers (actions/solvers.py, mirroring
pkg/scheduler/actions/common/solvers/job_solver.go:47-90) accumulate
victims one step at a time and simulate each prefix — one dispatch and
one fetch per scenario, with the device idle while the host prepares the
next, so worst-case reclaim latency is scenario-count-bound (SURVEY §7.6
/ BASELINE config #3 call this out).

This kernel evaluates ALL prefixes at once: prefix k's node state is the
live state plus the cumulative released resources of victims 1..k (an
eviction moves a victim's request into the releasing pool), and the
pending job's pipeline-only placement attempt vmaps over that leading
axis.  The result is a [K] feasibility vector from ONE device call; the
solver then exact-confirms only the smallest feasible prefix through the
ordinary statement path (validators, victim re-placement, masks), so
semantics stay identical to the sequential search.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .allocate import allocate_jobs_kernel
from .scoring import BINPACK


@functools.partial(jax.jit,
                   static_argnames=("num_prefixes", "gpu_strategy",
                                    "cpu_strategy"))
def batch_prefix_feasibility(node_allocatable, node_idle, node_releasing,
                             node_labels, node_taints, node_room,
                             release_step, release_node, release_vec,
                             task_req, task_job, task_selector,
                             task_tolerations, num_prefixes: int,
                             task_node_mask=None,
                             gpu_strategy: int = BINPACK,
                             cpu_strategy: int = BINPACK) -> jnp.ndarray:
    """[num_prefixes] bool: can the pending job pipeline onto each
    prefix's released resources?

    Victim releases arrive SPARSE — (release_step [M], release_node [M],
    release_vec [M,R]) rows, padded with step >= num_prefixes — and the
    dense per-prefix releasing pools materialize on device (scatter-add +
    cumulative sum over the prefix axis), so the host->device transfer is
    O(victim tasks), never O(prefixes x nodes).  node_room is
    prefix-invariant (evicted pods stay on their node as Releasing).
    """
    n = node_allocatable.shape[0]
    r = node_releasing.shape[1]
    delta = jnp.zeros((num_prefixes, n, r), node_releasing.dtype)
    delta = delta.at[release_step, release_node].add(release_vec,
                                                     mode="drop")
    prefix_rel = node_releasing[None, :, :] + jnp.cumsum(delta, axis=0)
    # Job 1 holds the caller's padding task rows; gate it off so the
    # kernel skips their placement work entirely (same convention as
    # session.propose_placements padding).
    job_allowed = jnp.array([True, False])

    def one(prefix):
        result = allocate_jobs_kernel(
            node_allocatable, node_idle, prefix, node_labels,
            node_taints, node_room, task_req, task_job, task_selector,
            task_tolerations, job_allowed,
            task_node_mask=task_node_mask,
            gpu_strategy=gpu_strategy, cpu_strategy=cpu_strategy,
            pipeline_only=True)
        return result.job_success[0]

    return jax.vmap(one)(prefix_rel)
