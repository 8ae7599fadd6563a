"""The scenario prescreen's oracle: the exact kernel's pipeline-only
attempt at every prefix, one dependent step a POD.

Until PR 45 this was the program's third form (``scan_prefixes`` in
``ops/scenario_batch.py``, the answer to a call with a ``task_node_mask``);
since the mask row is part of a run's identity no call of the program
reaches it, and it stays here as what the counted and the grouped form are
held to, with a mask and without."""

import jax
import jax.numpy as jnp

from kai_scheduler_tpu.ops.allocate import allocate_jobs_kernel


def scan_prefixes(prefix_rel, node_allocatable, node_idle, node_labels,
                  node_taints, node_room, task_req, task_job,
                  task_selector, task_tolerations, task_node_mask,
                  gpu_strategy: int, cpu_strategy: int):
    """[K] bool from ``prefix_rel`` [K,N,R]: ``allocate_jobs_kernel``
    vmapped over the prefixes, ``task_node_mask`` [T,N] bool or None."""
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
