"""Hierarchical DRF fair-share division.

Re-implements the behavior of the reference's proportion plugin division
algorithm (pkg/scheduler/plugins/proportion/resource_division/
resource_division.go:26-357 and proportion.go:403-440):

1. *Deserved phase*: every queue first receives min(deserved, requestable)
   (UNLIMITED deserved counts as the whole pool).
2. *Over-quota phase*: the remainder is divided within priority bands
   (higher priority first).  Within a band, repeated proportional rounds by
   usage-penalized over-quota weight ``w' = max(0, W' + k*(W' - U'))``
   (:245), each grant floored to whole units (:292); fractional remainders
   are then distributed one unit at a time, largest remainder first (:264).
3. *Hierarchy*: each parent's fair share becomes the pool divided among its
   children (proportion.go:410-425).

Three implementations, property-tested against each other:
- ``set_resources_share_np``: sequential numpy reference, one queue group.
- ``fair_share_levels``: jitted JAX kernel, ONE DISPATCH PER LEVEL.  Queue
  groups (siblings under one parent) become segment ids so every level of
  the hierarchy is one vectorized division over all groups at once;
  priority bands are a static unroll; the round loop is a
  ``lax.while_loop`` fixed point.
- ``fair_share_forest``: the whole forest as ONE jitted dispatch
  (docs/DESIGN.md §2b).  Levels pack into a dense ``[L, Qmax]`` layout
  (global queue indices, -1 padding), sibling groups stay segment ids with
  one shared padding dump group, priority bands fold into a
  ``lax.fori_loop``, and the level recursion (parent fair share feeds the
  children's pool) unrolls statically inside the single jit.  The host
  prep (``prepared_forest``) is cached across cycles keyed on the queue
  set + weights, so a steady 10k-queue cluster pays one dispatch and
  O(hash) host work per cycle.
"""

from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

UNLIMITED = -1.0
EPS = 1e-9
# Fractional remainders are quantized before largest-remainder ranking so
# that float-accumulation noise can't flip near-ties between the sequential
# reference and the vectorized kernel (the tiebreak rank then decides).
FRAC_DECIMALS = 9


# ---------------------------------------------------------------------------
# numpy reference (single group of sibling queues, all resources)
# ---------------------------------------------------------------------------

def _requestable(request, limit):
    return np.where(limit == UNLIMITED, request, np.minimum(limit, request))


def restore_exact(fair: np.ndarray, deserved: np.ndarray,
                  limit: np.ndarray, request: np.ndarray) -> np.ndarray:
    """``fair`` [Q,R] as the host holds quantities (f64), with what a
    32-bit device rounded away put back where it is known.

    Without x64 the kernels take the queues' deserved, limit and request
    as f32, and a sum past 2**24 units that is no multiple of a power of
    two is rounded there (a department that asks 590,852,000 milli-cores
    reads 590,851,968).  A queue that is given all it may ask, or exactly
    its deserved share, then reads half a unit in the last place beside
    what the host's own roll-up (f64, exact) holds for it, and every
    comparison of the two that upstream makes in one precision (reclaim's
    ``allocated / fair_share > 1``, ``allocated + request <= fair_share``)
    is decided by the rounding: a reclaimer that stands exactly at its
    share was refused.  So where the device's answer IS one of those two
    landing values at the device's precision, the answer is that value as
    the host has it.  No tolerance: an answer that is neither stays what
    the device said."""
    fair = np.asarray(fair)
    if fair.dtype == np.float64:
        return fair
    out = fair.astype(np.float64)
    for exact in (deserved, _requestable(request, limit)):
        out = np.where(fair == exact.astype(fair.dtype), exact, out)
    return out


def set_resources_share_np(total: np.ndarray, k_value: float,
                           deserved: np.ndarray, limit: np.ndarray,
                           over_quota_weight: np.ndarray,
                           request: np.ndarray, usage: np.ndarray,
                           priority: np.ndarray,
                           tiebreak_rank: np.ndarray | None = None
                           ) -> np.ndarray:
    """Sequential reference for one sibling group.

    Shapes: total [R]; per-queue arrays [Q,R] except priority [Q].
    Returns fair_share [Q,R].
    """
    q, r = deserved.shape
    if tiebreak_rank is None:
        tiebreak_rank = np.arange(q)
    fair = np.zeros((q, r))
    for res in range(r):
        fair[:, res] = _set_resource_share_np(
            float(total[res]), k_value, deserved[:, res], limit[:, res],
            over_quota_weight[:, res], request[:, res], usage[:, res],
            priority, tiebreak_rank)
    return fair


def _set_resource_share_np(total, k, deserved, limit, oqw, request, usage,
                           priority, tiebreak_rank):
    q = deserved.shape[0]
    requestable = _requestable(request, limit)
    # Phase 1: deserved-first (resource_division.go:92-109).
    eff_deserved = np.where(deserved == UNLIMITED, total, deserved)
    fair = np.minimum(eff_deserved, requestable)
    remaining = total - fair.sum()
    if remaining <= 0:
        return fair

    # Phase 2: over-quota by priority band (:111-144).
    bands = sorted(set(priority.tolist()), reverse=True)
    rem_frac = {b: np.zeros(q) for b in bands}  # remainder map per band
    for band in bands:
        in_band = priority == band
        while True:
            unsat = in_band & (requestable - fair > EPS)
            tw = oqw[unsat].sum()
            if tw <= 0:
                break
            n_w = np.where(unsat, oqw / tw, 0.0)
            share_w = np.where(unsat, np.maximum(0.0, n_w + k * (n_w - usage)),
                               0.0)
            sw = share_w.sum()
            if sw <= 0:
                break
            amount_this_round = remaining
            another_round = False
            for i in range(q):
                if not unsat[i] or oqw[i] == 0:
                    continue
                fair_i = amount_this_round * share_w[i] / sw
                rem_req = requestable[i] - fair[i]
                if rem_req <= fair_i:
                    give = rem_req
                    rem_frac[band][i] = 0.0
                else:
                    give = np.floor(fair_i)
                    rem_frac[band][i] = fair_i - give
                if give > 0:
                    fair[i] += give
                    remaining -= give
                another_round = another_round or rem_req < fair_i
            if not another_round or remaining <= EPS:
                break
        if remaining <= EPS:
            break

    # Phase 3: largest-remainder units, priority band order (:126-141,264-281).
    for band in bands:
        if remaining <= EPS:
            break
        entries = [(i, round(rem_frac[band][i], FRAC_DECIMALS))
                   for i in range(q) if rem_frac[band][i] > 0]
        entries.sort(key=lambda e: (-e[1], tiebreak_rank[e[0]]))
        for i, _ in entries:
            if remaining <= EPS:
                break
            give = min(1.0, remaining)
            fair[i] += give
            remaining -= give
    return fair


# ---------------------------------------------------------------------------
# JAX kernel: segment (multi-group) division, one hierarchy level
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LevelSpec:
    """Static structure of one hierarchy level (trace-time constants)."""
    num_groups: int
    num_bands: int
    max_rounds: int = 64


def _segment_sum(x, seg, num_groups):
    return jax.ops.segment_sum(x, seg, num_segments=num_groups)


@functools.partial(jax.jit, static_argnames=("spec",))
def divide_groups_jax(spec: LevelSpec, group_total, group_of_queue,
                      band_of_queue, deserved, limit, oqw, request, usage,
                      tiebreak_rank, k_value):
    """One level of fair-share: divide each group's total among its queues.

    Shapes: group_total [G,R]; group_of_queue/band_of_queue/tiebreak [Q];
    per-queue arrays [Q,R].  Returns fair [Q,R].

    Vectorization of the sequential reference: all sums become segment sums
    over the group axis, priority bands unroll statically, and the
    proportional rounds run as a while_loop until no group/resource wants
    another round.  Order-independence of each round (grants are computed
    from round-start state) makes this exactly equal to the sequential
    algorithm.
    """
    G, Q = spec.num_groups, group_of_queue.shape[0]
    R = deserved.shape[1]
    seg = group_of_queue

    requestable = jnp.where(limit == UNLIMITED, request,
                            jnp.minimum(limit, request))
    my_total = group_total[seg]  # [Q,R]
    eff_deserved = jnp.where(deserved == UNLIMITED, my_total, deserved)
    fair0 = jnp.minimum(eff_deserved, requestable)
    remaining0 = jnp.maximum(group_total - _segment_sum(fair0, seg, G), 0.0)

    def run_band(band, fair, remaining, rem_frac_all):
        in_band = (band_of_queue == band)[:, None]  # [Q,1]

        def cond(carry):
            fair, remaining, rem_frac, live, i = carry
            return jnp.any(live) & (i < spec.max_rounds)

        def body(carry):
            # ``live`` [G,R]: the (group, resource) pairs whose previous
            # round asked for another.  The sequential reference loops
            # per group and per resource, so a pair that is done must
            # sit out the rounds another pair still needs — a further
            # round would re-split its leftover and overwrite the
            # remainders its largest-remainder pass ranks by.
            fair, remaining, rem_frac, live, i = carry
            unsat = in_band & (requestable - fair > EPS)
            tw = _segment_sum(jnp.where(unsat, oqw, 0.0), seg, G)  # [G,R]
            n_w = jnp.where(unsat & (tw[seg] > 0), oqw / jnp.where(
                tw[seg] > 0, tw[seg], 1.0), 0.0)
            share_w = jnp.where(unsat,
                                jnp.maximum(0.0, n_w + k_value * (n_w - usage)),
                                0.0)
            sw = _segment_sum(share_w, seg, G)  # [G,R]
            active = unsat & (share_w > 0) & (sw[seg] > 0) & live[seg]
            fair_q = jnp.where(active,
                               remaining[seg] * share_w
                               / jnp.where(sw[seg] > 0, sw[seg], 1.0), 0.0)
            rem_req = requestable - fair
            satisfied_now = rem_req <= fair_q
            give = jnp.where(active,
                             jnp.where(satisfied_now, rem_req,
                                       jnp.floor(fair_q)), 0.0)
            new_frac = jnp.where(active,
                                 jnp.where(satisfied_now, 0.0,
                                           fair_q - jnp.floor(fair_q)),
                                 rem_frac)
            fair = fair + give
            remaining = jnp.maximum(
                remaining - _segment_sum(give, seg, G), 0.0)
            another = (active & (rem_req < fair_q)) & (remaining[seg] > EPS)
            live = _segment_sum(another.astype(fair.dtype), seg, G) > 0
            return fair, remaining, new_frac, live, i + 1

        fair, remaining, rem_frac, _, _ = jax.lax.while_loop(
            cond, body,
            (fair, remaining, rem_frac_all, jnp.ones((G, R), bool),
             jnp.array(0)))
        return fair, remaining, rem_frac

    # Static unroll over priority bands (band ids are dense 0..num_bands-1,
    # 0 = highest priority — computed by the host-side prep).
    rem_fracs = []
    fair, remaining = fair0, remaining0
    for band in range(spec.num_bands):
        fair, remaining, rem_frac = run_band(
            band, fair, remaining, jnp.zeros_like(fair0))
        rem_fracs.append(rem_frac)

    # Largest-remainder unit distribution, per band, per group, per resource.
    def distribute(fair, remaining, rem_frac):
        # rank within (group, resource) by (-frac, tiebreak); non-members
        # (frac == 0) sort last and receive nothing.
        member = rem_frac > 0.0  # [Q,R]

        def per_resource(fair_r, remaining_r, frac_r, member_r):
            frac_r = jnp.round(frac_r, FRAC_DECIMALS)
            # Sort by group, then -frac, then tiebreak.
            order = jnp.lexsort((tiebreak_rank, -frac_r,
                                 jnp.where(member_r, 0, 1), seg))
            sorted_seg = seg[order]
            pos = jnp.arange(Q)
            # Rank within group = position - first position of the group.
            is_start = jnp.concatenate([
                jnp.array([True]), sorted_seg[1:] != sorted_seg[:-1]])
            group_start = jnp.where(is_start, pos, 0)
            group_start = jax.lax.associative_scan(jnp.maximum, group_start)
            rank_sorted = pos - group_start
            rank = jnp.zeros(Q, jnp.int32).at[order].set(
                rank_sorted.astype(jnp.int32))
            amount = jnp.where(
                member_r,
                jnp.clip(remaining_r[seg] - rank.astype(fair_r.dtype),
                         0.0, 1.0),
                0.0)
            fair_r = fair_r + amount
            remaining_r = jnp.maximum(
                remaining_r - _segment_sum(amount, seg, G), 0.0)
            return fair_r, remaining_r

        outs = [per_resource(fair[:, r], remaining[:, r], rem_frac[:, r],
                             member[:, r]) for r in range(R)]
        fair = jnp.stack([o[0] for o in outs], axis=1)
        remaining = jnp.stack([o[1] for o in outs], axis=1)
        return fair, remaining

    for band in range(spec.num_bands):
        fair, remaining = distribute(fair, remaining, rem_fracs[band])
    return fair


# ---------------------------------------------------------------------------
# Hierarchy orchestration (host-side prep + per-level kernel calls)
# ---------------------------------------------------------------------------

@dataclass
class QueueHierarchy:
    """Host-side prep of the queue forest for the level-by-level kernel."""
    levels: list            # list of np.ndarray of queue indices per depth
    parent: np.ndarray      # [Q] int, -1 for roots
    band_of_queue: np.ndarray   # [Q] dense band index per level (global bands)
    num_bands: int
    tiebreak_rank: np.ndarray   # [Q]

    @classmethod
    def build(cls, parent: np.ndarray, priority: np.ndarray,
              creation: np.ndarray, uids: list[str] | None = None
              ) -> "QueueHierarchy":
        q = parent.shape[0]
        depth = np.zeros(q, np.int32)
        for i in range(q):
            d, p = 0, parent[i]
            while p >= 0:
                d += 1
                p = parent[p]
            depth[i] = d
        levels = [np.where(depth == d)[0]
                  for d in range(int(depth.max()) + 1 if q else 0)]
        # Dense band ids: 0 = highest priority.
        uniq = np.unique(priority)[::-1]
        band = np.searchsorted(-uniq, -priority)
        order = sorted(range(q), key=lambda i: (creation[i],
                                                uids[i] if uids else str(i)))
        rank = np.zeros(q, np.int64)
        for r_, i in enumerate(order):
            rank[i] = r_
        return cls(levels, parent.astype(np.int64), band.astype(np.int32),
                   len(uniq) if q else 1, rank)


def fair_share_levels(total: np.ndarray, k_value: float,
                      hierarchy: QueueHierarchy,
                      deserved: np.ndarray, limit: np.ndarray,
                      oqw: np.ndarray, request: np.ndarray,
                      usage: np.ndarray) -> np.ndarray:
    """Full hierarchical fair share: one kernel call per depth level.

    ``request`` must already be rolled up the parent chain (roll_up_requests).
    Returns fair share [Q,R] for every queue, leaf and interior alike.
    """
    q, r = deserved.shape
    fair = np.zeros((q, r))
    if q == 0:
        return fair
    from ..utils.metrics import METRICS
    for depth, idxs in enumerate(hierarchy.levels):
        if len(idxs) == 0:
            continue
        METRICS.inc("fairshare_dispatch_total")
        if depth == 0:
            group_of = np.zeros(len(idxs), np.int32)
            group_totals = total[None, :]
        else:
            parents = hierarchy.parent[idxs]
            uniq_parents, group_of = np.unique(parents, return_inverse=True)
            group_totals = fair[uniq_parents]
        spec = LevelSpec(num_groups=group_totals.shape[0],
                         num_bands=hierarchy.num_bands)
        # kaijit: disable=KJT001 — level widths follow the QUEUE
        # hierarchy (control-plane config: reconfig events, not
        # per-cycle live pod counts), so exact shapes here trade a
        # rare reconfig retrace for minimal per-level kernels; the
        # per-cycle hot path uses the bucketed forest entry points.
        out = divide_groups_jax(
            spec, jnp.asarray(group_totals), jnp.asarray(group_of),
            jnp.asarray(hierarchy.band_of_queue[idxs]),
            jnp.asarray(deserved[idxs]), jnp.asarray(limit[idxs]),
            jnp.asarray(oqw[idxs]), jnp.asarray(request[idxs]),
            jnp.asarray(usage[idxs]),
            jnp.asarray(hierarchy.tiebreak_rank[idxs]),
            k_value)
        fair[idxs] = np.asarray(out)
    return fair


# ---------------------------------------------------------------------------
# Queue-forest kernel: the WHOLE hierarchy in one jitted dispatch
# ---------------------------------------------------------------------------

# group_parent sentinel: a group whose pool is the cluster total (roots).
ROOT_GROUP = -1


@dataclass(frozen=True)
class ForestSpec:
    """Static structure of the whole queue forest (trace-time constants).

    ``level_dims[l] = (G_l, S_l)``: level l packs into a dense
    ``[G_l, S_l]`` sibling-group matrix (groups x max-siblings, slot -1
    padding).  Per-level tight dims keep the fused kernel's work at the
    per-level path's operand sizes instead of paying the deepest level's
    width at every depth.  ``level_bands[l]`` lists the dense band ids
    actually present among level l's queues: the band fold iterates only
    those (a band with no member queues is a no-op in the reference
    sweep — zero grants, zero remainders — so skipping it is exact)."""
    level_dims: tuple
    level_bands: tuple
    num_bands: int
    num_queues: int
    max_rounds: int = 64

    @property
    def num_levels(self) -> int:
        return len(self.level_dims)

    @property
    def padded_slots(self) -> int:
        return sum(g * s for g, s in self.level_dims)


@dataclass
class QueueForest:
    """Dense level-batched layout of one queue forest.

    Per level l (device-resident, uploaded once at build; the prep cache
    keeps them alive across cycles):
    - ``level_qidx[l]`` [G_l, S_l]: global queue index per slot, -1 pad;
    - ``level_parent[l]`` [G_l]: global queue index whose fair share is
      the group's pool, or ROOT_GROUP for the cluster total.
    Group order is ascending unique parent index and slot order within a
    group is ascending queue index — the same operand order the
    per-level path's segment reductions see (bit-parity, DESIGN §2b).
    """
    level_qidx: tuple
    level_parent: tuple


def build_forest(hierarchy: QueueHierarchy
                 ) -> tuple[ForestSpec, QueueForest]:
    """Pack a QueueHierarchy into the dense per-level group matrices."""
    num_q = hierarchy.parent.shape[0]
    dims, band_ids, qidx_arrays, parent_arrays = [], [], [], []
    for depth, idxs in enumerate(hierarchy.levels):
        if depth == 0 or len(idxs) == 0:
            parents = np.full(len(idxs), ROOT_GROUP, np.int64)
        else:
            parents = hierarchy.parent[idxs]
        present = np.unique(hierarchy.band_of_queue[idxs]) if len(idxs) \
            else np.zeros(1, np.int64)
        band_ids.append(tuple(int(b) for b in present))
        gp, g_of = np.unique(parents, return_inverse=True)
        G = max(1, len(gp))
        sizes = np.bincount(g_of, minlength=G).astype(np.int64)
        S = max(1, int(sizes.max()) if sizes.size else 1)
        qidx = np.full((G, S), -1, np.int32)
        # Slot = position within the group, in ascending queue order
        # (idxs ascending; np.unique's inverse preserves that order).
        slot = np.zeros(len(idxs), np.int64)
        seen = np.zeros(G, np.int64)
        for i, g in enumerate(g_of):
            slot[i] = seen[g]
            seen[g] += 1
        qidx[g_of, slot] = idxs
        dims.append((G, S))
        qidx_arrays.append(jnp.asarray(qidx))
        parent_arrays.append(jnp.asarray(
            (gp if len(gp) else np.array([ROOT_GROUP])).astype(np.int32)))
    if not dims:
        dims = [(1, 1)]
        band_ids = [(0,)]
        qidx_arrays = [jnp.full((1, 1), -1, jnp.int32)]
        parent_arrays = [jnp.full((1,), ROOT_GROUP, jnp.int32)]
    spec = ForestSpec(level_dims=tuple(dims), level_bands=tuple(band_ids),
                      num_bands=hierarchy.num_bands, num_queues=num_q)
    forest = QueueForest(tuple(qidx_arrays), tuple(parent_arrays))
    return spec, forest


def _divide_level_dense(spec: ForestSpec, bands: tuple, pool, band_q,
                        deserved, limit, oqw, request, usage,
                        tiebreak_rank, k_value):
    """One level's division over the dense [G, S, R] group layout.

    The same fixed-point math as ``divide_groups_jax`` with the segment
    machinery dissolved: segment sums become axis-1 row reductions (the
    accumulation visits siblings in the same ascending order), segment
    gathers become [G, 1, R] broadcasts, and the in-group
    largest-remainder ranking becomes a per-row lexsort.  No scatter or
    gather appears anywhere in the round loop — the CPU/TPU cost of the
    old per-level kernel was dominated by 3 scatter-adds per round.
    Priority bands fold into a ``fori_loop`` carrying a [B, G, S, R]
    remainder stack.  Padding slots carry all-zero inputs: requestable
    0 keeps them unsatisfied-never-active through every phase, and
    trailing +0.0 terms cannot change a row reduction's value."""
    G, S = pool.shape[0], deserved.shape[1]
    R = deserved.shape[2]

    requestable = jnp.where(limit == UNLIMITED, request,
                            jnp.minimum(limit, request))
    my_total = pool[:, None, :]  # [G,1,R] broadcast
    eff_deserved = jnp.where(deserved == UNLIMITED,
                             jnp.broadcast_to(my_total, deserved.shape),
                             deserved)
    fair0 = jnp.minimum(eff_deserved, requestable)
    remaining0 = jnp.maximum(pool - fair0.sum(axis=1), 0.0)  # [G,R]

    def run_band(band, fair, remaining, rem_frac0):
        in_band = (band_q == band)[:, :, None]  # [G,S,1]

        def cond(carry):
            fair, remaining, rem_frac, live, i = carry
            return jnp.any(live) & (i < spec.max_rounds)

        def body(carry):
            # ``live`` [G,R] as in ``divide_groups_jax``: a (group,
            # resource) pair runs a round only while it asked for one.
            fair, remaining, rem_frac, live, i = carry
            unsat = in_band & (requestable - fair > EPS)
            tw = jnp.where(unsat, oqw, 0.0).sum(axis=1)  # [G,R]
            tw_b = tw[:, None, :]
            n_w = jnp.where(unsat & (tw_b > 0), oqw / jnp.where(
                tw_b > 0, tw_b, 1.0), 0.0)
            share_w = jnp.where(unsat,
                                jnp.maximum(0.0,
                                            n_w + k_value * (n_w - usage)),
                                0.0)
            sw = share_w.sum(axis=1)[:, None, :]  # [G,1,R]
            active = unsat & (share_w > 0) & (sw > 0) & live[:, None, :]
            fair_q = jnp.where(active,
                               remaining[:, None, :] * share_w
                               / jnp.where(sw > 0, sw, 1.0), 0.0)
            rem_req = requestable - fair
            satisfied_now = rem_req <= fair_q
            give = jnp.where(active,
                             jnp.where(satisfied_now, rem_req,
                                       jnp.floor(fair_q)), 0.0)
            new_frac = jnp.where(active,
                                 jnp.where(satisfied_now, 0.0,
                                           fair_q - jnp.floor(fair_q)),
                                 rem_frac)
            fair = fair + give
            remaining = jnp.maximum(remaining - give.sum(axis=1), 0.0)
            another = (active & (rem_req < fair_q)) \
                & (remaining[:, None, :] > EPS)
            return fair, remaining, new_frac, another.any(axis=1), i + 1

        fair, remaining, rem_frac, _, _ = jax.lax.while_loop(
            cond, body,
            (fair, remaining, rem_frac0, jnp.ones((G, R), bool),
             jnp.array(0)))
        return fair, remaining, rem_frac

    # Band fold: a fori_loop over the band ids actually present at this
    # level (dense, descending-priority order), not 0..num_bands-1 — an
    # absent band's sweep grants nothing and leaves no remainders, so
    # skipping it is exactly the reference's no-op.
    band_vec = jnp.asarray(bands, jnp.int32)
    n_bands = len(bands)

    def band_body(bi, carry):
        fair, remaining, rem_frac_all = carry
        fair, remaining, rem_frac = run_band(
            band_vec[bi], fair, remaining, jnp.zeros_like(fair))
        rem_frac_all = rem_frac_all.at[bi].set(rem_frac)
        return fair, remaining, rem_frac_all

    fair, remaining, rem_frac_all = jax.lax.fori_loop(
        0, n_bands, band_body,
        (fair0, remaining0, jnp.zeros((n_bands, G, S, R))))

    def distribute(fair, remaining, rem_frac):
        member = rem_frac > 0.0  # [G,S,R]

        def per_resource(fair_r, remaining_r, frac_r, member_r):
            # [G,S] each; remaining_r [G].
            frac_r = jnp.round(frac_r, FRAC_DECIMALS)
            order = jnp.lexsort((tiebreak_rank, -frac_r,
                                 jnp.where(member_r, 0, 1)), axis=-1)
            # order is a per-row permutation; its argsort is the inverse
            # permutation = each slot's in-group largest-remainder rank.
            rank = jnp.argsort(order, axis=-1)
            amount = jnp.where(
                member_r,
                jnp.clip(remaining_r[:, None] - rank.astype(fair_r.dtype),
                         0.0, 1.0),
                0.0)
            fair_r = fair_r + amount
            remaining_r = jnp.maximum(
                remaining_r - amount.sum(axis=1), 0.0)
            return fair_r, remaining_r

        outs = [per_resource(fair[:, :, r], remaining[:, r],
                             rem_frac[:, :, r], member[:, :, r])
                for r in range(R)]
        fair = jnp.stack([o[0] for o in outs], axis=2)
        remaining = jnp.stack([o[1] for o in outs], axis=1)
        return fair, remaining

    def dist_body(bi, carry):
        fair, remaining = carry
        return distribute(fair, remaining, rem_frac_all[bi])

    fair, remaining = jax.lax.fori_loop(0, n_bands, dist_body,
                                        (fair, remaining))
    return fair


@functools.partial(jax.jit, static_argnames=("spec",))
def fair_share_forest_jax(spec: ForestSpec, level_qidx, level_parent,
                          band_of, deserved, limit, oqw,
                          request, usage, tiebreak_rank, total, k_value):
    """The whole hierarchical division as one jitted program.

    Per-queue arrays are the global (unpadded) [Q,R] stacks; padding
    happens here by appending one zero row every padded slot gathers
    (zero request/weight/deserved makes a padding slot inert in every
    phase).  Levels unroll statically at their own [G_l, S_l] shapes:
    level l's group pools gather the fair shares level l-1 just wrote,
    which is exactly the per-level recursion of ``fair_share_levels``
    fused into one dispatch."""
    Q = spec.num_queues
    R = deserved.shape[1]
    zrow = jnp.zeros((1, R), deserved.dtype)
    des_p = jnp.concatenate([deserved, zrow])
    lim_p = jnp.concatenate([limit, zrow])
    oqw_p = jnp.concatenate([oqw, zrow])
    req_p = jnp.concatenate([request, zrow])
    use_p = jnp.concatenate([usage, zrow])
    band_p = jnp.concatenate([band_of, jnp.zeros(1, band_of.dtype)])
    tie_p = jnp.concatenate(
        [tiebreak_rank, jnp.full((1,), Q, tiebreak_rank.dtype)])

    fair_all = jnp.zeros((Q + 1, R))
    for level in range(spec.num_levels):
        qidx = level_qidx[level]               # [G,S]
        valid = qidx >= 0
        qi = jnp.where(valid, qidx, Q)         # padding reads the zero row
        gp = level_parent[level]               # [G]
        pool = jnp.where((gp >= 0)[:, None],
                         fair_all[jnp.clip(gp, 0, Q)],
                         jnp.broadcast_to(total, (gp.shape[0], R)))
        out = _divide_level_dense(
            spec, spec.level_bands[level], pool, band_p[qi], des_p[qi],
            lim_p[qi], oqw_p[qi], req_p[qi], use_p[qi], tie_p[qi],
            k_value)
        # Padding slots all write the zero row at index Q (identical
        # values, so duplicate-index scatter order cannot matter).
        fair_all = fair_all.at[qi.reshape(-1)].set(
            jnp.where(valid[:, :, None], out, 0.0).reshape(-1, R))
    return fair_all[:Q]


@dataclass
class ForestPrep:
    """Arena-resident host prep for one queue forest: the built
    hierarchy, the dense layout, and the device-resident slow-moving
    tensors (weights and the hierarchy's band/tiebreak vectors) that are
    part of the cache key and therefore constant for the cache entry's
    lifetime.  Only ``request``/``usage`` move cycle to cycle."""
    hierarchy: QueueHierarchy
    spec: ForestSpec
    forest: QueueForest
    deserved: jnp.ndarray
    limit: jnp.ndarray
    oqw: jnp.ndarray
    band_of: jnp.ndarray
    tiebreak: jnp.ndarray


def fair_share_forest(total: np.ndarray, k_value: float, prep: ForestPrep,
                      request: np.ndarray, usage: np.ndarray
                      ) -> np.ndarray:
    """Full hierarchical fair share in ONE kernel dispatch.

    Same contract as ``fair_share_levels`` (``request`` rolled up the
    parent chain; returns [Q,R] for every queue) — property-tested
    bit-identical against it on randomized forests."""
    q = request.shape[0]
    if q == 0:
        return np.zeros((q, request.shape[1] if request.ndim == 2
                         else 0))
    from ..utils.metrics import METRICS
    METRICS.inc("fairshare_dispatch_total")
    out = fair_share_forest_jax(
        prep.spec, prep.forest.level_qidx, prep.forest.level_parent,
        prep.band_of, prep.deserved, prep.limit, prep.oqw,
        jnp.asarray(request), jnp.asarray(usage), prep.tiebreak,
        jnp.asarray(total), k_value)
    return np.asarray(out)


# Host-prep memo: (queue set, priorities, creations, weights) -> built
# hierarchy + forest layout + resident weight tensors.  A steady cluster
# re-divides every cycle with unchanged structure; rebuilding the
# O(Q·depth) hierarchy prep and re-uploading the layout and weights each
# time was pure waste.  Bounded LRU: churn between a few shapes (chaos
# suites, sharded pools) stays cached.  _FOREST_LOCK serializes the
# cache AND the guard-watch init: concurrent sharded schedulers call
# prepared_forest from their own cycle threads (chaos_matrix --shards),
# and an unlocked OrderedDict corrupts under interleaved
# get/move_to_end/popitem.
_FOREST_CACHE: OrderedDict = OrderedDict()
_FOREST_CACHE_MAX = 8
_FOREST_LOCK = threading.Lock()
_GUARD_WATCH = None


def prepared_forest(parent: np.ndarray, priority: np.ndarray,
                    creation: np.ndarray, uids: list[str],
                    deserved: np.ndarray, limit: np.ndarray,
                    oqw: np.ndarray, out_info: dict | None = None
                    ) -> ForestPrep:
    """Build (or reuse) the host prep for one queue forest.

    The cache key is the full queue-set identity (uids, parents,
    priorities, creation stamps) plus the quota weights, so any change
    to the forest shape or weights rebuilds while steady cycles pay one
    hash (``fairshare_prep_reuse_total``).  A device-guard transition
    (breaker flip or closed-breaker fallback) drops the cache: the
    resident weight tensors may sit on the dead side of the fallback
    boundary, same hazard the arena invalidates on.

    ``out_info`` (optional dict) receives ``{"reused": bool}`` for THIS
    call — a per-call verdict the global counter cannot give once
    concurrent shards share the cache."""
    global _GUARD_WATCH
    import hashlib

    from ..framework.arena import GuardWatch
    from ..utils.deviceguard import device_guard
    from ..utils.metrics import METRICS
    h = hashlib.blake2b(digest_size=16)
    for arr in (parent, priority, creation, deserved, limit, oqw):
        h.update(np.ascontiguousarray(arr).tobytes())
    h.update("\x00".join(uids).encode())
    key = h.digest()
    with _FOREST_LOCK:
        if _GUARD_WATCH is None:
            _GUARD_WATCH = GuardWatch()
        if _GUARD_WATCH.transitioned(device_guard()):
            _FOREST_CACHE.clear()
        hit = _FOREST_CACHE.get(key)
        if out_info is not None:
            out_info["reused"] = hit is not None
        if hit is not None:
            _FOREST_CACHE.move_to_end(key)
            METRICS.inc("fairshare_prep_reuse_total")
            return hit
        # Build under the lock: concurrent shards share one queue set,
        # so racing threads would build the same entry twice; the loser
        # of an unlocked race would also evict the winner's live entry.
        hierarchy = QueueHierarchy.build(parent, priority, creation, uids)
        spec, forest = build_forest(hierarchy)
        prep = ForestPrep(hierarchy, spec, forest, jnp.asarray(deserved),
                          jnp.asarray(limit), jnp.asarray(oqw),
                          jnp.asarray(hierarchy.band_of_queue),
                          jnp.asarray(hierarchy.tiebreak_rank))
        _FOREST_CACHE[key] = prep
        while len(_FOREST_CACHE) > _FOREST_CACHE_MAX:
            _FOREST_CACHE.popitem(last=False)
        return prep


def roll_up_requests(parent: np.ndarray, leaf_values: np.ndarray
                     ) -> np.ndarray:
    """Aggregate per-leaf quantities up the parent chain
    (proportion.go:378-401: Request/Allocated accumulate on every ancestor)."""
    q = parent.shape[0]
    # Deepest-first so each child's (already complete) subtotal flows up.
    accum = leaf_values.copy()
    for i in sorted(range(q), key=lambda i: -_depth_of(parent, i)):
        p = parent[i]
        if p >= 0:
            accum[p] += accum[i]
    return accum


def _depth_of(parent: np.ndarray, i: int) -> int:
    d, p = 0, parent[i]
    while p >= 0:
        d += 1
        p = parent[p]
    return d


# ---------------------------------------------------------------------------
# DRF dominant share (queue_resource_share.go:142-162)
# ---------------------------------------------------------------------------

NO_FAIR_SHARE_DRF_MULTIPLIER = 1000.0


def dominant_share(allocated: np.ndarray, allocatable: np.ndarray,
                   total: np.ndarray) -> np.ndarray:
    """max over resources of allocated/allocatable; zero allocatable with
    allocation gets the penalty multiplier.  [Q,R],[Q,R],[R] -> [Q]."""
    xp = jnp if isinstance(allocated, jnp.ndarray) else np
    alloc_share = xp.where(allocatable == UNLIMITED,
                           xp.broadcast_to(total, allocated.shape),
                           allocatable)
    value = xp.where(alloc_share > 0, allocated / xp.where(
        alloc_share > 0, alloc_share, 1.0),
        allocated * NO_FAIR_SHARE_DRF_MULTIPLIER)
    return value.max(axis=1)


def allocatable_share(deserved: np.ndarray, fair: np.ndarray,
                      limit: np.ndarray) -> np.ndarray:
    """GetAllocatableShare (resource_share.go:52-62): max(deserved, fair)
    capped at limit; UNLIMITED deserved -> limit."""
    xp = jnp if isinstance(deserved, jnp.ndarray) else np
    base = xp.maximum(deserved, fair)
    capped = xp.where(limit == UNLIMITED, base, xp.minimum(limit, base))
    return xp.where(deserved == UNLIMITED, limit, capped)
