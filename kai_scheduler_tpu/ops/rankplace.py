"""Rank-aware gang placement: rank -> node assignment WITHIN a gang.

Rank-Aware Resource Scheduling for Tightly-Coupled MPI Workloads on
Kubernetes (arxiv 2603.22691) measures whole-percentage job-runtime wins
from keeping consecutive MPI ranks topology-adjacent: rank r and rank
r+1 exchange the most traffic (halo exchanges, ring all-reduce), so the
mean "hop distance" between consecutive ranks' nodes is the latency the
collective actually pays.

The fill-plan kernels (ops/allocate_grouped.py) decide WHICH node slots
a gang occupies; this module decides WHICH RANK lands on which of those
slots.  Because the slot multiset is fixed, the assignment can never
change feasibility or capacity accounting — it is a pure permutation of
interchangeable tasks (the caller proves interchangeability; the
topology plugin re-checks it).

Algorithm: hierarchical-order assignment.  Nodes get a *topology rank*
— their position in the lexicographic order of their domain-id path
(top level first, node index last) — and the gang's slots are stably
sorted by it; ranks 0..T-1 then map to slots in that order.  For a tree
metric this is optimal: any ordering that keeps each subtree's slots
contiguous crosses every domain boundary exactly once, which is the
minimum number of crossings any rank sequence can achieve, and the hop
metric below counts exactly those crossings.  Determinism: the sort is
stable with the slot index as the final tie-break, so the same snapshot
produces the same assignment, bit for bit.

Two implementations, bit-identical (tests/test_rankplace.py sweeps
randomized instances under KAI_FAULT_SEED):

- ``rank_place_kernel``: one jitted dispatch — a stable ``lax.sort`` of
  (topology-rank, slot-index) pairs plus the per-level hop fold — the
  in-kernel scoring home the fused per-group-step fill feeds;
- ``rank_place_np``: the host reference (``np.lexsort`` is the same
  stable sort), which is also the small-gang fast path (a 4-wide gang is
  cheaper on host than a dispatch).

Hop metric: hop(a, b) = 0 for the same node, else 1 + the number of
topology levels whose domains differ (a missing label counts as
differing — an unlabeled node is adjacent to nothing).  Same rack = 1,
same block different rack = 2, different block = 3, and so on — the
tree distance in boundary crossings.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .topology import ROOT_LEVEL, TopologyTree

# Gangs of at least this many slots take the kernel, smaller ones the
# host reference — both paths bit-identical, the threshold is purely a
# dispatch-overhead choice.
_KERNEL_MIN_GANG = 32


@dataclass
class TopoOrder:
    """Per-snapshot topology ordering of the packed node axis.

    ``topo_rank[i]``: node i's position in the hierarchical DFS order
    (unlabeled nodes and padding rows sort last, in index order).
    ``level_segs``: [L, N_pad] int32 domain id per level (top level
    first), -1 where the node lacks the label chain — the hop metric's
    operand.  Both derive purely from the TopologyTree, so they are
    built once per session and reused across gangs.
    """
    topo_rank: np.ndarray          # [N_pad] int32
    level_segs: np.ndarray         # [L, N_pad] int32
    num_levels: int


def build_topo_order(tree: TopologyTree, n_pad: int) -> TopoOrder:
    """Topology ordering for one tree over the packed node axis."""
    n = tree.node_domain[ROOT_LEVEL].shape[0]
    levels = [lv for lv in tree.levels if lv in tree.node_domain]
    segs = np.full((max(len(levels), 1), n_pad), -1, np.int32)
    if not levels:
        segs = segs[:0]
    for li, lv in enumerate(levels):
        segs[li, :n] = tree.node_domain[lv]
    # Lexicographic hierarchical order: top level primary, deeper levels
    # refine, node index breaks ties (np.lexsort: LAST key is primary).
    # Unlabeled domains (-1) map past every real id so they sort last
    # within their prefix; padding rows sort after all real nodes.
    keys = []
    for li in range(len(levels) - 1, -1, -1):
        col = segs[li, :n]
        keys.append(np.where(col < 0, np.int64(2 ** 31 - 1),
                             col.astype(np.int64)))
    # lexsort is a composition of stable sorts: nodes sharing a full
    # domain path keep ascending index order without an explicit key.
    order = np.lexsort(tuple(keys)) if keys else np.arange(n)
    topo_rank = np.empty(n_pad, np.int32)
    topo_rank[order] = np.arange(n, dtype=np.int32)
    topo_rank[n:] = np.arange(n, n_pad, dtype=np.int32)
    return TopoOrder(topo_rank, segs, len(levels))


def _hops_np(nodes_by_rank: np.ndarray, level_segs: np.ndarray
             ) -> np.ndarray:
    """[T-1] hop distances between consecutive ranks' nodes."""
    a, b = nodes_by_rank[:-1], nodes_by_rank[1:]
    if a.size == 0:
        return np.zeros(0, np.int32)
    same = a == b
    if level_segs.shape[0] == 0:
        diff = np.zeros(a.shape[0], np.int32)
    else:
        sa, sb = level_segs[:, a], level_segs[:, b]
        diff = ((sa != sb) | (sa < 0) | (sb < 0)).sum(
            axis=0).astype(np.int32)
    return np.where(same, 0, 1 + diff).astype(np.int32)


def rank_place_np(slot_nodes: np.ndarray, topo_rank: np.ndarray,
                  level_segs: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Host reference (and the small-gang path).

    ``slot_nodes``: [T] packed node index per gang slot.  Returns
    (perm [T] int32 — slot index for rank position k, hops [T-1] int32
    between consecutive ranks AFTER assignment).
    """
    t = slot_nodes.shape[0]
    perm = np.lexsort((np.arange(t), topo_rank[slot_nodes])).astype(
        np.int32)
    return perm, _hops_np(slot_nodes[perm], level_segs)


@jax.jit
def rank_place_kernel(slot_nodes, valid, topo_rank, level_segs):
    """One jitted dispatch: stable sort by (topology rank, slot index)
    plus the hop fold.  Formula-identical to ``rank_place_np`` — a
    stable single-key sort with the index as the value IS lexsort with
    the index tie-break.

    ``valid`` masks padding slots (the caller pads the gang axis to a
    pow2 bucket so fleets of varied gang sizes share compilations, the
    convention every hot-path kernel here follows): padding keys map
    past every real topology rank (< N_pad < 2^31), so the stable sort
    parks them after all real slots and the first ``sum(valid)`` rows
    of the output equal the unpadded result exactly."""
    t = slot_nodes.shape[0]
    key = jnp.where(valid, topo_rank[slot_nodes],
                    jnp.int32(2 ** 31 - 1))
    idx = jnp.arange(t, dtype=jnp.int32)
    _, perm = jax.lax.sort((key, idx), dimension=0, is_stable=True,
                           num_keys=1)
    nodes_sorted = slot_nodes[perm]
    a, b = nodes_sorted[:-1], nodes_sorted[1:]
    same = a == b
    if level_segs.shape[0] == 0:
        diff = jnp.zeros(a.shape, jnp.int32)
    else:
        sa, sb = level_segs[:, a], level_segs[:, b]
        diff = ((sa != sb) | (sa < 0) | (sb < 0)).sum(
            axis=0).astype(jnp.int32)
    hops = jnp.where(same, 0, 1 + diff).astype(jnp.int32)
    return perm, hops


def rank_place_padded(slot_nodes: np.ndarray, topo_rank, level_segs
                      ) -> tuple[np.ndarray, np.ndarray]:
    """Kernel rung with pow2 gang-axis bucketing: pads, dispatches,
    slices — returns exactly ``rank_place_np``'s (perm [T], hops
    [T-1]).  This is the thunk the plugin hands to dispatch_kernel."""
    t = slot_nodes.shape[0]
    t_pad = _KERNEL_MIN_GANG
    while t_pad < t:
        t_pad *= 2
    padded = np.zeros(t_pad, np.int32)
    padded[:t] = slot_nodes
    valid = np.zeros(t_pad, bool)
    valid[:t] = True
    perm, hops = rank_place_kernel(
        jnp.asarray(padded), jnp.asarray(valid),
        jnp.asarray(topo_rank), jnp.asarray(level_segs))
    return perm[:t], hops[:max(t - 1, 0)]


def resolve_mode(gang_size: int) -> str:
    """kernel | host, by the gang's size."""
    return "kernel" if gang_size >= _KERNEL_MIN_GANG else "host"


def mean_hop(nodes_by_rank: np.ndarray, order: TopoOrder) -> float:
    """Measured mean consecutive-rank hop distance of one assignment —
    the scale-ring scenario's adjacency metric (and the number the
    rank-oblivious baseline is compared on)."""
    hops = _hops_np(np.asarray(nodes_by_rank), order.level_segs)
    return float(hops.mean()) if hops.size else 0.0
