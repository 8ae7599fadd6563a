"""Vectorized predicate masks: tasks × nodes feasibility in one shot.

Replaces the reference's per-task-per-node predicate chain
(pkg/scheduler/plugins/predicates/predicates.go:106,
pkg/scheduler/k8s_internal/predicates/predicates.go:70-167 and
NodeInfo.IsTaskAllocatable node_info.go:168) with dense tensor ops over the
packed snapshot: resource capacity, node-selector/affinity label matching,
taint/toleration, and pod-count room all evaluate as one boolean program
under jit.

``feasibility_row`` is the canonical single-task implementation; the gang
allocation kernel steps it per task against mutating node state, and the
batch [T, N] form is its vmap — one definition, no drift between paths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NO_LABEL = -1
NO_TAINT = -1
EPS = 1e-9


def hard_row(labels, taints, room, selector, tolerations):
    """One task against all nodes, [N] bool: what no release can change
    — every constrained label matches, every taint is tolerated, and a
    pod of room is left."""
    sel_ok = jnp.all((selector[None, :] == NO_LABEL)
                     | (selector[None, :] == labels), axis=-1)
    tol = jnp.any(taints[:, :, None] == tolerations[None, None, :], axis=-1)
    taint_ok = jnp.all((taints == NO_TAINT) | tol, axis=-1)
    return sel_ok & taint_ok & (room >= 1.0)


def feasibility_row(idle, releasing, labels, taints, room,
                    req, selector, tolerations):
    """One task against all nodes: ([N,R] state, [R]/[L]/[Tl] task) ->
    (fit_now [N], fit_future [N]).

    fit_now: IsTaskAllocatable (idle resources); fit_future:
    IsTaskAllocatableOnReleasingOrIdle (pipelining candidates).
    """
    hard = hard_row(labels, taints, room, selector, tolerations)
    fit_now = hard & jnp.all(req[None, :] <= idle + EPS, axis=-1)
    fit_future = hard & jnp.all(req[None, :] <= idle + releasing + EPS,
                                axis=-1)
    return fit_now, fit_future


@jax.jit
def feasibility_masks(node_idle, node_releasing, node_labels, node_taints,
                      node_pod_room, task_req, task_selector,
                      task_tolerations):
    """Batch predicate evaluation: vmap of feasibility_row over tasks.
    Returns (fit_now, fit_future): [T,N] bool masks."""
    return jax.vmap(
        lambda req, sel, tol: feasibility_row(
            node_idle, node_releasing, node_labels, node_taints,
            node_pod_room, req, sel, tol)
    )(task_req, task_selector, task_tolerations)


def feasibility_caps_row(idle, releasing, labels, taints, room,
                         req, selector, tolerations):
    """Fused single-pass variant of ``feasibility_row`` + the grouped
    kernel's whole-task capacity math: one read of the node state yields
    (fit_now, fit_future, cap_now_f, cap_tot_f), each [N].

    The resource axis is unrolled (R is static and small), so XLA sees a
    single elementwise DAG per node instead of a chain of [N,R]
    broadcast+reduce ops — the per-group-step formulation the fused
    allocation kernel (ops/allocate_grouped) runs inside its scan.  The
    float semantics are formula-identical to ``feasibility_row``:
    comparisons against ``idle + EPS``, capacity as floor(idle/req)
    bounded later by the caller; min/all over R reassociate only exact
    operations (min is exact; the boolean chain is order-free).

    ``releasing=None`` declares the caller has proven the releasing pool
    empty: fit_future and cap_tot_f alias the fit-now outputs (with
    releasing == 0 ``feasibility_row``'s formulas reduce to exactly
    that, including EPS behaviour).
    """
    hard = hard_row(labels, taints, room, selector, tolerations)

    r_dims = idle.shape[1]
    fits_idle = hard
    fits_total = hard
    cap_now_f = None
    cap_tot_f = None
    inf = jnp.asarray(jnp.inf, idle.dtype)
    for r in range(r_dims):
        rq = req[r]
        safe = jnp.where(rq > 0, rq, 1.0)
        col = idle[:, r]
        fits_idle = fits_idle & (rq <= col + EPS)
        ratio = jnp.where(rq > 0, jnp.floor(col / safe), inf)
        cap_now_f = ratio if cap_now_f is None \
            else jnp.minimum(cap_now_f, ratio)
        if releasing is not None:
            tot = col + releasing[:, r]
            fits_total = fits_total & (rq <= tot + EPS)
            ratio_t = jnp.where(rq > 0, jnp.floor(tot / safe), inf)
            cap_tot_f = ratio_t if cap_tot_f is None \
                else jnp.minimum(cap_tot_f, ratio_t)
    if releasing is None:
        return fits_idle, fits_idle, cap_now_f, cap_now_f
    return fits_idle, fits_total, cap_now_f, cap_tot_f


# -- standalone sub-masks (used directly by tests/tools) --------------------

@jax.jit
def selector_mask(node_labels: jnp.ndarray,
                  task_selector: jnp.ndarray) -> jnp.ndarray:
    """[N,L] x [T,L] -> [T,N] bool: every constrained label matches."""
    t_sel = task_selector[:, None, :]   # [T,1,L]
    n_lab = node_labels[None, :, :]     # [1,N,L]
    ok = (t_sel == NO_LABEL) | (t_sel == n_lab)
    return jnp.all(ok, axis=-1)


@jax.jit
def toleration_mask(node_taints: jnp.ndarray,
                    task_tolerations: jnp.ndarray) -> jnp.ndarray:
    """[N,Tt] x [T,Tl] -> [T,N] bool: every node taint is tolerated."""
    taints = node_taints[None, :, :, None]        # [1,N,Tt,1]
    tols = task_tolerations[:, None, None, :]     # [T,1,1,Tl]
    tolerated = jnp.any(taints == tols, axis=-1)  # [T,N,Tt]
    ok = (node_taints[None, :, :] == NO_TAINT) | tolerated
    return jnp.all(ok, axis=-1)


@jax.jit
def capacity_mask(node_free: jnp.ndarray, task_req: jnp.ndarray
                  ) -> jnp.ndarray:
    """[N,R] x [T,R] -> [T,N] bool: request fits into free resources."""
    return jnp.all(task_req[:, None, :] <= node_free[None, :, :] + EPS,
                   axis=-1)
