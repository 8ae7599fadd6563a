"""Topology-aware scheduling (TAS): domain trees as segment ops.

Re-designs pkg/scheduler/plugins/topology/ for the device: the reference
walks a pointer tree of domains per job (job_filtering.go:34-111,
calcSubTreeFreeResources :192, calcNodeAccommodation :213,
getJobAllocatableDomains :265, sortTree :460, getJobRatioToFreeResources
:491); here every topology level is a segment-id vector over the node axis,
so per-domain free-resource aggregation and gang-accommodation counting are
``segment_sum``s over the packed node state — one fused kernel per level
instead of a tree walk per job.

Semantics preserved:
- a domain fits a gang iff the gang's total request fits the domain's
  idle+releasing pool AND enough whole pods fit stackwise on its nodes;
- candidate levels run from the preferred level up to the required level
  (calculateRelevantDomainLevels :381-424); required-only means exactly
  that level; preferred-only climbs to the root;
- fitting domains are ordered most-packed-first (ratio of requested to
  free, descending — bin-pack, docs/topology/README.md:50-53), ties by
  domain id;
- a job with running pods and a required constraint is pinned to the
  domains already hosting its pods (getRelevantDomainsWithAllocatedPods);
- nodes inside preferred-level domains get a Topology-tier score boost
  (node_scoring.go:17-55) scaled by domain rank.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from .scoring import TOPOLOGY

ROOT_LEVEL = "__root__"
# Ratio assigned when a required resource doesn't exist in the domain.
IMPOSSIBLE_RATIO = 1e9


@dataclass
class TopologyTree:
    """Host-side encoding of one Topology CRD over the packed node axis."""
    name: str
    levels: list                      # deepest-last label keys, as in CRD
    # Per level: [N] int32 domain index (-1 = node lacks the label chain).
    node_domain: dict = field(default_factory=dict)   # level -> np.ndarray
    domain_names: dict = field(default_factory=dict)  # level -> [id->path]
    # Per level below the root: label-value path (a tuple) -> domain id.
    domain_ids: dict = field(default_factory=dict)
    # level -> ``domain_slots`` of it: a memo of a pure function of
    # ``node_domain``, the one thing a shared tree still gains.
    slots: dict = field(default_factory=dict)

    def num_domains(self, level: str) -> int:
        return len(self.domain_names.get(level, []))


def build_tree(name: str, levels: list, node_names: list,
               node_labels_by_name: dict) -> TopologyTree:
    """Group nodes into domains per level.  A domain's identity is the
    label-value path from the top level down (topology_structs.go:20-94)."""
    tree = TopologyTree(name, list(levels))
    n = len(node_names)
    # Root level: every node in domain 0.
    tree.node_domain[ROOT_LEVEL] = np.zeros(n, np.int32)
    tree.domain_names[ROOT_LEVEL] = ["root"]
    path_so_far = [() for _ in range(n)]
    for depth, label_key in enumerate(levels):
        ids: dict[tuple, int] = {}
        seg = np.full(n, -1, np.int32)
        names = []
        for i, node in enumerate(node_names):
            value = node_labels_by_name.get(node, {}).get(label_key)
            if value is None or path_so_far[i] is None:
                path_so_far[i] = None
                continue
            path_so_far[i] = path_so_far[i] + (value,)
            key = path_so_far[i]
            if key not in ids:
                ids[key] = len(names)
                names.append("/".join(key))
            seg[i] = ids[key]
        tree.node_domain[label_key] = seg
        tree.domain_names[label_key] = names
        tree.domain_ids[label_key] = ids
    return tree


# The session product (``Session.products``) the trees are kept under.
_KEPT_TREES = "topology_trees"


def _rows_hold(tree: TopologyTree, ssn, rows: list) -> bool:
    """True where each of ``rows`` still lies in the domains ``tree`` has
    it in: its node's level labels, read again, give the tree's own path.
    ``build_tree``'s rule row by row: a missing label ends the chain."""
    nodes, names = ssn.cluster.nodes, ssn.snapshot.node_names
    levels = tree.levels
    ids = [tree.domain_ids[level] for level in levels]
    have = [tree.node_domain[level][rows].tolist() for level in levels]
    for k, i in enumerate(rows):
        node = nodes.get(names[i])
        labels = node.labels if node is not None else {}
        path = ()
        for depth, level in enumerate(levels):
            value = labels.get(level) if path is not None else None
            if value is None:
                path, want = None, -1
            else:
                path = path + (value,)
                want = ids[depth].get(path)
            if want != have[depth][k]:
                return False
    return True


def session_trees(ssn) -> tuple[dict, int | None]:
    """``({name: TopologyTree}, rows checked or None)`` for one session.

    The trees are a function of the node order, the nodes' level labels
    and the Topology specs.  Where the session's pack was a patch
    (``ssn.patched_rows``: same cluster, same node order, and a node whose
    row is not among them was not touched), the trees of the session
    before are taken over once the specs are equal and every patched
    row's labels have been read again: a node replaced by one of the same
    name and hardware is a patched row, and a label no pod selects on is
    in no vocabulary, so that part of the proof is made here.  A row that
    differs builds the trees anew, as does any full pack (which empties
    ``ssn.products``) and a session with no arena.  Shared trees are
    read-only."""
    # In the cluster's order: the first tree is a job's default.
    spec = tuple((name, tuple(topo.get("levels", [])))
                 for name, topo in ssn.cluster.topologies.items())
    rows = ssn.patched_rows
    kept = ssn.products.get(_KEPT_TREES)
    if rows is not None and kept is not None and kept[0] == spec:
        rows = rows.tolist()
        trees = kept[1]
        if all(_rows_hold(tree, ssn, rows) for tree in trees.values()):
            return trees, len(rows)
    node_names = ssn.snapshot.node_names
    node_labels = {name: ssn.cluster.nodes[name].labels
                   for name in node_names if name in ssn.cluster.nodes}
    trees = {name: build_tree(name, list(levels), node_names, node_labels)
             for name, levels in spec}
    for tree in trees.values():
        for seg in tree.node_domain.values():
            seg.setflags(write=False)
    ssn.products[_KEPT_TREES] = (spec, trees)
    return trees, None


def domain_holds(free, pods, total_req, gang_size):
    """[..., D] bool: does a domain hold the gang?  THE rule, for the
    host's ``subset_nodes`` (numpy, one state) and the device's prescreen
    (``ops/scenario_batch.py`` ``domain_verdicts``, a state a prefix)
    alike: the gang's whole request fits the domain's idle + releasing
    sums ``free`` [..., D, R], and ``gang_size`` of its largest pod stack
    into the domain's nodes, ``pods`` [..., D] the sum over them of the
    whole pods each takes (capped at the gang): ``domain_aggregates``'
    quotient on the host's one state, the prescreen's ``stack_count`` on
    a prefix's, which is that quotient set right where a 32-bit division
    rounds across a whole number (ROADMAP D12).  Necessary for a
    placement inside the domain, not sufficient: the kernel decides."""
    return (pods >= gang_size) & (total_req <= free + 1e-9).all(axis=-1)


def domain_slots(seg: np.ndarray, n_pad: int):
    """``(slot_node [D_pad * S_pad] int32, D_pad)`` of one level: its
    domains as the rows of a table, a row the nodes of a domain in
    ascending node index, padded with ``n_pad`` (no node): the columns to
    a power of two, the rows to one up to 8 and to a multiple of 8 beyond
    (a prefix's pool holds a row a domain: 1,536 racks padded to 2,048
    would be a third more fleet to write and read).  The layout the
    device's domain form reads the fleet in (a domain a contiguous row,
    so no segment op over [K,N])."""
    from .allocate_grouped import _next_pow2
    member = np.flatnonzero(seg >= 0)
    if member.size == 0:
        return np.full(1, n_pad, np.int32), 1
    doms = seg[member]
    order = np.argsort(doms, kind="stable")
    member, doms = member[order], doms[order]
    sizes = np.bincount(doms)
    d = len(sizes)
    d_pad = _next_pow2(d) if d <= 8 else -(-d // 8) * 8
    s_pad = _next_pow2(int(sizes.max()))
    column = np.arange(member.size) - np.repeat(
        np.cumsum(sizes) - sizes, sizes)
    slot_node = np.full(d_pad * s_pad, n_pad, np.int32)
    slot_node[doms * s_pad + column] = member
    return slot_node, d_pad


@functools.partial(jax.jit, static_argnames=("num_domains",))
def domain_aggregates(node_free, node_room, seg, max_pod_req, gang_size,
                      num_domains: int):
    """Per-domain (free [D,R], pod-accommodation count [D]).

    Accommodation mirrors calcNodeAccommodation: per node, how many
    max-sized gang pods stack into idle+releasing resources, summed over
    the domain (capped at gang_size per node).
    """
    member = seg >= 0
    seg_safe = jnp.where(member, seg, 0)
    free = jax.ops.segment_sum(
        jnp.where(member[:, None], node_free, 0.0), seg_safe,
        num_segments=num_domains)
    per_res = jnp.where(max_pod_req[None, :] > 0,
                        jnp.floor(node_free / jnp.where(
                            max_pod_req[None, :] > 0, max_pod_req[None, :],
                            1.0)),
                        jnp.inf)
    fit = jnp.min(per_res, axis=1)
    fit = jnp.minimum(fit, node_room)
    fit = jnp.clip(fit, 0.0, gang_size)
    pods = jax.ops.segment_sum(jnp.where(member, fit, 0.0), seg_safe,
                               num_segments=num_domains)
    return free, pods


class TopologySession:
    """Per-session TAS state: registered by the topology plugin."""

    def __init__(self, ssn):
        self.ssn = ssn
        # {name: TopologyTree}, possibly the session before's and then
        # shared: never written.
        self.trees, self.rows_checked = session_trees(ssn)
        # job uid -> [N] preferred-level score boosts (set by subset_nodes).
        # kairace: single-writer=main
        self._job_node_scores: dict[str, np.ndarray] = {}
        # tree name -> rankplace.TopoOrder (built lazily, once per
        # session: pure function of the tree + packed node order).
        # kairace: single-writer=main
        self._topo_orders: dict[str, object] = {}

    # -- constraint resolution ---------------------------------------------
    def _job_constraint(self, job, podset=None):
        """Podset-level constraints override the job-level ones
        (subgroup TopologyConstraint, topology_plugin.go)."""
        required = job.required_topology_level
        preferred = job.preferred_topology_level
        topo_name = job.topology_name
        if podset is not None and podset.has_own_topology_constraint():
            required = podset.required_topology_level
            preferred = podset.preferred_topology_level
            topo_name = podset.topology_name or topo_name
        tree = self.trees.get(topo_name or next(iter(self.trees), ""))
        if tree is None:
            return None
        if not required and not preferred:
            return None
        return tree, required, preferred

    def _relevant_levels(self, tree: TopologyTree, required, preferred):
        """calculateRelevantDomainLevels: deepest -> root, collect from
        preferred/required until required (inclusive)."""
        ordered = list(reversed(tree.levels)) + [ROOT_LEVEL]
        out, collecting = [], False
        for level in ordered:
            if level == preferred or level == required:
                collecting = True
            if collecting:
                out.append(level)
            if level == required:
                break
        return out

    def _pinned_domains(self, job, tree, required, podset=None):
        """The domains of the required level that already host running
        pods of the podset(s) being allocated
        (getRelevantDomainsWithAllocatedPods takes the podSets under
        allocation, not the whole job), or None where nothing pins."""
        if not required or required not in tree.node_domain:
            return None
        pods = (podset.pods.values() if podset is not None
                else job.pods.values())
        active_nodes = {t.node_name for t in pods
                        if t.is_active_allocated() and t.node_name}
        if not active_nodes:
            return None
        ssn, seg_req = self.ssn, tree.node_domain[required]
        return {int(seg_req[ssn.node_index(node)])
                for node in active_nodes
                if ssn.node_index(node) >= 0
                and seg_req[ssn.node_index(node)] >= 0}

    def required_domains(self, job):
        """What the scenario prescreen needs of a job's REQUIRED level:
        ``(level, slot_node, domain_ok [D_pad] bool, domains, preferred)``
        (``domain_slots``; ``domain_ok`` false on the padding rows and,
        for a job pinned by its running pods, on every other domain), or
        None where the job requires no level of a tree the session has.
        The job-level constraint only: a podset's own is not asked
        here."""
        constraint = self._job_constraint(job)
        if constraint is None:
            return None
        tree, required, preferred = constraint
        seg = tree.node_domain.get(required) if required else None
        if seg is None or required == ROOT_LEVEL:
            return None
        n_pad = self.ssn.node_idle.shape[0]
        if required not in tree.slots:
            tree.slots[required] = domain_slots(seg, n_pad)
        slot_node, d_pad = tree.slots[required]
        domains = tree.num_domains(required)
        domain_ok = np.zeros(d_pad, bool)
        pinned = self._pinned_domains(job, tree, required)
        if pinned is None:
            domain_ok[:domains] = True
        else:
            domain_ok[sorted(pinned)] = True
        return required, slot_node, domain_ok, domains, bool(preferred)

    # -- the SubsetNodes extension point -----------------------------------
    def subset_nodes(self, job, tasks, podset=None):
        constraint = self._job_constraint(job, podset)
        if constraint is None:
            return None
        tree, required, preferred = constraint
        ssn = self.ssn
        n_pad = ssn.node_idle.shape[0]
        n = len(ssn.snapshot.node_names)

        reqs = np.stack([ssn._task_row(t)[0] for t in tasks]) \
            if tasks else np.zeros((1, ssn.node_idle.shape[1]))
        total_req = reqs.sum(axis=0)
        max_pod_req = reqs.max(axis=0)
        gang_size = len(tasks)
        node_free = (ssn.node_idle + ssn.node_releasing)[:n]
        node_room = ssn.node_room[:n]

        # Pin to domains already hosting the job's running pods when
        # required is set.
        pinned_domains = self._pinned_domains(job, tree, required, podset)

        candidates = []  # (level_rank, ratio, domain_name, mask)
        self._job_node_scores.pop(job.uid, None)
        for level_rank, level in enumerate(
                self._relevant_levels(tree, required, preferred)):
            seg = tree.node_domain.get(level)
            if seg is None:
                continue
            d = tree.num_domains(level)
            if d == 0:
                continue
            free, pods = domain_aggregates(
                jnp.asarray(node_free), jnp.asarray(node_room),
                jnp.asarray(seg), jnp.asarray(max_pod_req),
                float(gang_size), d)
            free = np.asarray(free)
            holds = domain_holds(free, np.asarray(pods), total_req,
                                 gang_size)
            for dom in np.flatnonzero(holds).tolist():
                if pinned_domains is not None and level == required \
                        and dom not in pinned_domains:
                    continue
                ratio = _pack_ratio(total_req, free[dom])
                mask = np.zeros(n_pad, bool)
                mask[:n] = seg == dom
                if pinned_domains is not None and level != required:
                    # Sub/ancestor domains must intersect the pinned set.
                    seg_req = tree.node_domain[required]
                    pin_mask = np.isin(seg_req, list(pinned_domains))
                    if not np.any(mask[:n] & pin_mask):
                        continue
                candidates.append(
                    (level_rank, -ratio, tree.domain_names[level][dom],
                     mask))

        if not candidates:
            job.add_fit_error(
                f"no topology domain of {tree.name} can host the gang "
                f"(required={required}, preferred={preferred})")
            return []
        candidates.sort(key=lambda c: (c[0], c[1], c[2]))

        # Preferred-level boost: nodes of better-ranked preferred domains
        # score higher (node_scoring.go).
        if preferred:
            boosts = np.zeros(n_pad)
            rank = 0
            for level_rank, _, _, mask in candidates:
                if level_rank == 0:  # preferred level entries come first
                    boosts = np.maximum(
                        boosts, mask * (TOPOLOGY / (rank + 1)))
                    rank += 1
            self._job_node_scores[job.uid] = boosts

        return [mask for _, _, _, mask in candidates]

    # -- rank-aware placement (ops/rankplace.py) ---------------------------
    def _topo_order_for(self, tree):
        from . import rankplace as rp
        order = self._topo_orders.get(tree.name)
        if order is None:
            order = rp.build_topo_order(tree, self.ssn.node_idle.shape[0])
            self._topo_orders[tree.name] = order
        return order

    def assign_ranks(self, tasks, placements):
        """Rank-aware reorder of one placed gang chunk
        (ssn.rank_assign_fns contract): returns the permuted
        [(task, node, piped)] list, or None to keep the rank-oblivious
        assignment.

        Preconditions verified here (cheap, O(gang)):
        - every task carries a distinct non-negative rank;
        - the tasks are interchangeable (identical request vector,
          node selector, and toleration set) — permuting them across
          the fill plan's slots then changes nothing but which rank
          runs where.
        The (node, piped) pairs permute as units: pipelined-ness
        belongs to the slot's capacity phase, not the task.
        """
        from ..utils.metrics import METRICS
        from ..utils.tracing import TRACER
        from . import rankplace as rp
        if len(placements) < 2 or not self.trees:
            return None
        chunk = [t for t, _n, _p in placements]
        ranks = [t.rank for t in chunk]
        if min(ranks) < 0 or len(set(ranks)) != len(ranks):
            return None
        t0 = chunk[0]
        req0 = t0.res_req.to_vec(mig_as_gpu=False)
        for t in chunk[1:]:
            if (t.node_selector != t0.node_selector
                    or t.tolerations != t0.tolerations
                    or not np.array_equal(
                        t.res_req.to_vec(mig_as_gpu=False), req0)):
                return None
        job = self.ssn.cluster.podgroups.get(t0.job_id)
        topo_name = getattr(job, "topology_name", None) if job else None
        tree = self.trees.get(topo_name) if topo_name else None
        if tree is None:
            tree = next(iter(self.trees.values()))
        order = self._topo_order_for(tree)
        ssn = self.ssn
        slot_nodes = np.empty(len(placements), np.int32)
        for i, (_t, node_name, _p) in enumerate(placements):
            idx = ssn.node_index(node_name)
            if idx < 0:
                return None
            slot_nodes[i] = idx
        mode = rp.resolve_mode(len(placements))
        with TRACER.span("rankplace", kind="rankplace",
                         gang=len(placements), tree=tree.name,
                         mode=mode) as sp:
            if mode == "kernel":
                t_len = len(placements)
                # rank_place_padded buckets the gang axis to pow2 so
                # fleets of varied gang sizes share one compilation.
                perm, hops = ssn.dispatch_kernel(
                    lambda: rp.rank_place_padded(
                        slot_nodes, order.topo_rank, order.level_segs),
                    label="rank_place",
                    validate=lambda r: getattr(
                        r[0], "shape", (0,))[0] == t_len)
                perm = np.asarray(perm)
                hops = np.asarray(hops)
            else:
                perm, hops = rp.rank_place_np(
                    slot_nodes, order.topo_rank, order.level_segs)
            mean = float(hops.mean()) if hops.size else 0.0
            sp.set(mean_hop=round(mean, 3))
        METRICS.inc("rank_place_assignments_total", mode=mode)
        METRICS.set_gauge("rank_place_mean_hop", mean)
        by_rank = sorted(range(len(chunk)), key=lambda i: chunk[i].rank)
        return [(chunk[by_rank[k]], placements[int(perm[k])][1],
                 placements[int(perm[k])][2])
                for k in range(len(placements))]

    # -- the extra-score extension point -----------------------------------
    def extra_scores(self, tasks):
        """The job's preferred-level boosts: one [N] row that holds for
        every task of the chunk (all of one job), or None."""
        if not tasks:
            return None
        return self._job_node_scores.get(tasks[0].job_id)


def _pack_ratio(total_req: np.ndarray, free: np.ndarray) -> float:
    """getJobRatioToFreeResources: dominant requested/free ratio."""
    ratio = 0.0
    for i in range(total_req.shape[0]):
        if total_req[i] <= 0:
            continue
        if free[i] <= 0:
            ratio = max(ratio, IMPOSSIBLE_RATIO)
        else:
            ratio = max(ratio, float(total_req[i] / free[i]))
    return ratio
