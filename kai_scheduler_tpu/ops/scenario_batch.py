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

A prefix is answered in one of two ways, chosen in the program from what
the task rows hold (``uniform_gang``), never by a flag:

* **counted** — the gang's real rows (job 0) are all one pod: same
  request, selector and tolerations, and no ``task_node_mask``.  A
  pipeline-only placement takes ``req`` from the chosen node's releasing
  pool and one pod of its room and nothing anywhere else, so node n
  takes exactly ``c_n`` pods whatever the order or the score, and the
  gang fits iff the hard-feasible nodes' ``c_n`` sum to its size.  One
  elementwise pass over the pools and one reduction over N.
* **scanned** — any other gang (a master beside its workers, a [T,N]
  mask): the pending job's pipeline-only placement attempt,
  ``allocate_jobs_kernel``, vmapped over the prefixes.  A count is exact
  for one group only: a second group's fit depends on where the first
  landed.

Both read the same dense per-prefix pools (scatter-add of the release
rows, running sum over the prefix axis).  The counted form could be had
from the touched nodes alone without ever materialising them; the pools
stay dense because the benchmark's byte count for this program
(``prefix_feasibility_bytes``: one [K,N,R] f32 pool written and read
once) is what its roofline share is measured against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .allocate import allocate_jobs_kernel
from .predicates import EPS, hard_row
from .scoring import BINPACK


def uniform_gang(task_req, task_job, task_selector, task_tolerations):
    """Scalar bool: is every row of job 0 the same pod as row 0?  The one
    predicate behind the choice of form: the device evaluates it on its
    operands, the host (numpy rows) on what it sends, to label the call."""
    real = task_job == 0
    same = real
    for rows in (task_req, task_selector, task_tolerations):
        same = same & (rows == rows[0]).all(axis=-1)
    return (same == real).all()


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


def count_prefixes(prefix_rel, node_idle, node_labels, node_taints,
                   node_room, task_req, task_job, task_selector,
                   task_tolerations):
    """The counted form: [K] bool from ``prefix_rel`` [K,N,R] for a gang
    of ``sum(task_job == 0)`` pods, each the pod of row 0."""
    req = task_req[0]
    need = jnp.sum(task_job == 0).astype(node_idle.dtype)
    hard = hard_row(node_labels, node_taints, node_room, task_selector[0],
                    task_tolerations[0])
    # Prefix-invariant, [N]: evicted pods stay on their node as Releasing.
    count = jnp.where(hard, jnp.floor(node_room), 0.0)[None, :]
    # R unrolled, a [K,N] plane a resource (feasibility_caps_row's way):
    # the compiler keeps N minor in the pool, and 3 would waste the lanes.
    for res in range(prefix_rel.shape[2]):
        rq = req[res]
        safe = jnp.where(rq > 0, rq, 1.0)
        total = node_idle[None, :, res] + prefix_rel[:, :, res]
        fits = corrected_count(jnp.floor(total / safe), safe, total)
        count = jnp.where(rq > 0, jnp.minimum(count, fits), count)
    # No node takes more than the gang: the sum stays small and exact.
    placed = jnp.sum(jnp.minimum(count, need), axis=1)
    return (need > 0) & (placed >= need)


def scan_prefixes(prefix_rel, node_allocatable, node_idle, node_labels,
                  node_taints, node_room, task_req, task_job,
                  task_selector, task_tolerations, task_node_mask,
                  gpu_strategy: int, cpu_strategy: int):
    """The scanned form: [K] bool from ``prefix_rel`` [K,N,R], the exact
    kernel's pipeline-only attempt at each prefix."""
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

    A gang of identical pods is counted, any other scanned (module
    docstring); a ``task_node_mask`` is static and goes to the scan.
    """
    def pools():
        n, r = node_releasing.shape
        delta = jnp.zeros((num_prefixes, n, r), node_releasing.dtype)
        delta = delta.at[release_step, release_node].add(release_vec,
                                                         mode="drop")
        return node_releasing[None, :, :] + jnp.cumsum(delta, axis=0)

    def scanned():
        return scan_prefixes(
            pools(), node_allocatable, node_idle, node_labels,
            node_taints, node_room, task_req, task_job, task_selector,
            task_tolerations, task_node_mask, gpu_strategy, cpu_strategy)

    if task_node_mask is not None:
        return scanned()

    def counted():
        return count_prefixes(pools(), node_idle, node_labels,
                              node_taints, node_room, task_req, task_job,
                              task_selector, task_tolerations)

    # At the top level, outside any vmap, where a cond would turn into a
    # select and run both.
    return jax.lax.cond(
        uniform_gang(task_req, task_job, task_selector, task_tolerations),
        counted, scanned)
