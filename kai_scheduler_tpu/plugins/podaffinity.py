"""Pod-affinity plugin: full inter-pod (anti-)affinity semantics.

Mirrors the reference's use of the upstream InterPodAffinity plugin
(pkg/scheduler/k8s_internal/predicates/predicates.go:70-167 wires
PreFilter/Filter; pkg/scheduler/api/pod_affinity/ keeps per-node pod
affinity metadata) re-designed for the tensor path: every
(selector, topologyKey, namespaces) term becomes a [N] node mask via
domain occupancy — "does this node's domain contain a pod matching the
selector" — computed from the live cluster state and memoized on the
session's mutation tick.

Semantics covered:
- REQUIRED pod affinity: the task may only go where a matching pod's
  domain is.  When the match can come from the task's own gang (a chunk
  member matches the term), enforcement moves INTO the allocation kernel
  (ops/allocate.py task_aff_domain: union-of-marker-domains + the
  upstream first-pod bootstrap rule), since a static mask cannot see
  in-gang placements.
- REQUIRED pod anti-affinity: domains containing matching pods are
  excluded; SYMMETRY is honored — an existing pod's anti-affinity term
  also repels an incoming task that matches it.  In-gang spread runs in
  the kernel (task_anti_domain marker/avoider carry).
- Namespace scoping: a term matches only pods in its resolved namespace
  list (the owner pod's own namespace unless the manifest listed some).
- PREFERRED terms contribute ±weight-scaled score on matching domains.
- Legacy coarse peers (``pod_affinity_peers`` job-uid lists) keep their
  score behavior.
"""

from __future__ import annotations

import numpy as np

from ..utils.metrics import METRICS
from ..utils.tracing import TRACER
from .base import Plugin, register_plugin

AFFINITY_SCORE = 50.0  # between placement (<=9+10) and availability (100)
HOSTNAME_KEY = "kubernetes.io/hostname"


def _same_term(a, b) -> bool:
    return (a.topology_key == b.topology_key and a.selector == b.selector
            and a.expressions == b.expressions
            and a.namespaces == b.namespaces)


@register_plugin("podaffinity")
class PodAffinityPlugin(Plugin):
    def on_session_open(self, ssn) -> None:
        self.ssn = ssn
        self._domain_cache: dict = {}
        self._pods_cache = (-1, None)  # (mutation_count, pods)
        METRICS.inc("podaffinity_pod_walks_total", 0)
        ssn.extra_score_fns.append(self.extra_scores)
        ssn.hard_node_mask_fns.append(self.hard_masks)
        ssn.anti_domain_fns.append(self.anti_domains)
        ssn.affinity_domain_fns.append(self.affinity_domains)

    # -- domain encoding ---------------------------------------------------
    def _domains(self, topology_key: str) -> tuple[np.ndarray, int]:
        """[N] int32 domain id per node for one topology key (-1 = node
        lacks the label).  hostname is every node its own domain.
        Node labels are immutable within a session, so memoized."""
        cached = self._domain_cache.get(topology_key)
        if cached is not None:
            return cached
        cluster = self.ssn.cluster
        names = self.ssn.snapshot.node_names
        n = self.ssn.node_idle.shape[0]
        dom = np.full(n, -1, np.int32)
        ids: dict[str, int] = {}
        for i, name in enumerate(names):
            node = cluster.nodes.get(name)
            if node is None:
                continue
            if topology_key == HOSTNAME_KEY:
                value = name
            else:
                value = node.labels.get(topology_key)
            if value is None:
                continue
            dom[i] = ids.setdefault(value, len(ids))
        self._domain_cache[topology_key] = (dom, len(ids))
        return dom, len(ids)

    def _active_pods(self):
        """(labels, namespace, node_idx, anti_terms, job_id) for every
        active allocated pod on a snapshot node; memoized per session
        mutation tick (statements bump it on every state change).  For
        the terms of a chunk's own tasks, which match on every pod's
        labels; the symmetry gate asks ``_carrier_repellers``."""
        tick = self.ssn.mutation_count
        if self._pods_cache[0] == tick:
            return self._pods_cache[1]
        METRICS.inc("podaffinity_pod_walks_total")
        out = []
        for pg in self.ssn.cluster.podgroups.values():
            for task in pg.pods.values():
                if not task.is_active_allocated() or not task.node_name:
                    continue
                idx = self.ssn.node_index(task.node_name)
                if idx < 0:
                    continue
                out.append((task.labels, task.namespace, idx,
                            getattr(task, "anti_affinity_terms", []),
                            task.job_id))
        self._pods_cache = (tick, out)
        return out

    def _carrier_repellers(self) -> list:
        """``(node_idx, term)`` for each required anti-affinity term of an
        active allocated pod on a snapshot node, from the pods the
        snapshot layer found carrying a term (``Session.term_carriers``),
        filtered at the call because statements change status inside a
        session."""
        out = []
        for task in self.ssn.term_carriers:
            if not (task.anti_affinity_terms and task.is_active_allocated()
                    and task.node_name):
                continue
            idx = self.ssn.node_index(task.node_name)
            if idx >= 0:
                out.extend((idx, term) for term in task.anti_affinity_terms)
        return out

    def _term_mask(self, term, pods) -> np.ndarray:
        """[N] bool: nodes whose domain holds a pod matching the term."""
        dom, n_dom = self._domains(term.topology_key)
        if n_dom == 0:
            return np.zeros(self.ssn.node_idle.shape[0], bool)
        has = np.zeros(n_dom, bool)
        for labels, ns, idx, _anti, _job in pods:
            if dom[idx] >= 0 and term.matches(labels, ns):
                has[dom[idx]] = True
        mask = np.zeros(dom.shape[0], bool)
        valid = dom >= 0
        mask[valid] = has[dom[valid]]
        return mask

    @staticmethod
    def _in_gang(term, tasks) -> bool:
        """Can the term be satisfied/violated by the chunk itself?"""
        return any(term.matches(x.labels, x.namespace) for x in tasks)

    def _selected_in_gang_affinity(self, tasks):
        """The ONE in-gang required-affinity term the kernel enforces
        dynamically (affinity_domains); deterministic first-by-task-order
        so hard_masks and affinity_domains agree on which term that is."""
        for task in tasks:
            for t2 in getattr(task, "affinity_terms", []) or []:
                if self._in_gang(t2, tasks):
                    return t2
        return None

    # -- hard masks (required terms vs EXISTING pods) ----------------------
    def hard_masks(self, tasks):
        has_own_terms = any(
            getattr(t, "affinity_terms", None)
            or getattr(t, "anti_affinity_terms", None)
            for t in tasks)
        # Anti-affinity symmetry: existing pods' anti terms repel a
        # matching incoming task from their domains.
        sym_repellers = self._carrier_repellers()
        TRACER.stamp("propose:operands", affinity=(
            "walked" if has_own_terms
            else "carriers" if self.ssn.term_carriers else "none"))
        if not has_own_terms and not sym_repellers:
            return None
        # Only a chunk with terms of its own needs every running pod's
        # labels.
        pods = self._active_pods() if has_own_terms else None

        n = self.ssn.node_idle.shape[0]
        out = np.ones((len(tasks), n), bool)
        touched = False
        selected = self._selected_in_gang_affinity(tasks)
        for i, task in enumerate(tasks):
            row = out[i]
            for term in getattr(task, "affinity_terms", []) or []:
                if selected is not None and _same_term(term, selected):
                    continue  # enforced in-kernel via affinity_domains
                if self._in_gang(term, tasks):
                    # A second distinct in-gang term: the kernel carries
                    # only one, so enforce it statically against existing
                    # pods with the first-pod bootstrap escape.
                    mask = self._term_mask(term, pods)
                    if not mask.any() and term.matches(task.labels,
                                                       task.namespace):
                        continue
                    row &= mask
                    touched = True
                    continue
                row &= self._term_mask(term, pods)
                touched = True
            for term in getattr(task, "anti_affinity_terms", []) or []:
                row &= ~self._term_mask(term, pods)
                touched = True
            for idx, term in sym_repellers:
                if term.matches(task.labels, task.namespace):
                    dom, n_dom = self._domains(term.topology_key)
                    if dom[idx] >= 0:
                        row &= ~(dom == dom[idx])
                        touched = True
        return out if touched else None

    # -- in-gang REQUIRED anti-affinity ------------------------------------
    def anti_domains(self, tasks):
        """(dom [T,N], marks [T], avoids [T]) for a required anti term
        some chunk member carries that some chunk member matches.  One
        term per chunk (multiple distinct in-gang terms are rare; the
        first active one wins — cross-gang enforcement still comes from
        hard_masks)."""
        term = None
        for task in tasks:
            for t2 in getattr(task, "anti_affinity_terms", []) or []:
                if self._in_gang(t2, tasks):
                    term = t2
                    break
            if term is not None:
                break
        if term is None:
            return None
        dom, n_dom = self._domains(term.topology_key)
        if n_dom == 0:
            return None
        doms = np.tile(dom, (len(tasks), 1))
        marks = np.array([term.matches(t.labels, t.namespace)
                          for t in tasks])
        avoids = np.array([
            any(_same_term(t3, term)
                for t3 in getattr(t, "anti_affinity_terms", []) or [])
            for t in tasks])
        return doms, marks, avoids

    # -- in-gang REQUIRED affinity -----------------------------------------
    def affinity_domains(self, tasks):
        """(dom [T,N], marks, avoids, static_ok [T,N], bootstrap [T]) for
        a required affinity term satisfiable by the chunk itself: avoiders
        must share a domain with a matching pod — pre-existing
        (static_ok), placed by this gang (kernel union), or themselves
        under the upstream first-pod bootstrap rule."""
        term = self._selected_in_gang_affinity(tasks)
        if term is None:
            return None
        dom, n_dom = self._domains(term.topology_key)
        if n_dom == 0:
            return None
        pods = self._active_pods()
        static_row = self._term_mask(term, pods)
        t_count = len(tasks)
        doms = np.tile(dom, (t_count, 1))
        static_ok = np.tile(static_row, (t_count, 1))
        marks = np.array([term.matches(t.labels, t.namespace)
                          for t in tasks])
        avoids = np.array([
            any(_same_term(t3, term)
                for t3 in getattr(t, "affinity_terms", []) or [])
            for t in tasks])
        no_existing = not static_row.any()
        bootstrap = marks & avoids & no_existing
        return doms, marks, avoids, static_ok, bootstrap

    # -- scores (preferred terms + legacy peers) ---------------------------
    def _job_nodes(self, job_uid: str) -> set:
        pg = self.ssn.cluster.podgroups.get(job_uid)
        if pg is None:
            return set()
        return {self.ssn.node_index(t.node_name)
                for t in pg.pods.values()
                if t.is_active_allocated() and t.node_name}

    def extra_scores(self, tasks):
        n = self.ssn.node_idle.shape[0]
        out = None
        pods = None
        for i, task in enumerate(tasks):
            peers = getattr(task, "pod_affinity_peers", None) or []
            anti = getattr(task, "pod_anti_affinity_peers", None) or []
            pref = getattr(task, "preferred_affinity_terms", None) or []
            pref_anti = getattr(task, "preferred_anti_affinity_terms",
                                None) or []
            if not (peers or anti or pref or pref_anti):
                continue
            if out is None:
                out = np.zeros((len(tasks), n))
            for uid in peers:
                for idx in self._job_nodes(uid):
                    if idx >= 0:
                        out[i, idx] += AFFINITY_SCORE
            for uid in anti:
                for idx in self._job_nodes(uid):
                    if idx >= 0:
                        out[i, idx] -= AFFINITY_SCORE
            if pref or pref_anti:
                if pods is None:
                    pods = self._active_pods()
                for term in pref:
                    out[i] += (term.weight * AFFINITY_SCORE
                               * self._term_mask(term, pods))
                for term in pref_anti:
                    out[i] -= (term.weight * AFFINITY_SCORE
                               * self._term_mask(term, pods))
        return out
