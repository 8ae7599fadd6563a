"""Topology-aware scheduling (TAS) plugin.

Registers ops/topology.TopologySession's domain filtering as the
SubsetNodes extension point and its preferred-level boosts as score terms
(mirroring pkg/scheduler/plugins/topology/topology_plugin.go:43-50).
"""

from __future__ import annotations

from .base import Plugin, register_plugin


@register_plugin("topology")
class TopologyPlugin(Plugin):
    def on_session_open(self, ssn) -> None:
        if not ssn.cluster.topologies:
            return
        from ..ops.topology import TopologySession
        from ..utils.metrics import METRICS
        from ..utils.tracing import TRACER
        self._topo = TopologySession(ssn)
        # How often the trees outlive the session (ops/topology.py
        # ``session_trees``; docs/OBSERVABILITY.md).
        checked = self._topo.rows_checked
        if checked is None:
            METRICS.inc("topology_tree_built_total")
            TRACER.stamp(f"plugin:{self.name}", tree="built")
        else:
            METRICS.inc("topology_tree_reused_total")
            TRACER.stamp(f"plugin:{self.name}", tree="reused",
                         rows_checked=checked)
        ssn.subset_nodes_fns.append(self._topo.subset_nodes)
        # The same level's domains for the scenario prescreen, whose
        # verdict applies subset_nodes' rule a prefix (ops/topology.py
        # ``domain_holds``).
        ssn.required_domain_fns.append(self._topo.required_domains)
        ssn.extra_score_fns.append(self.extra_scores)
        # Rank-aware gang placement (ops/rankplace.py): reorder an
        # interchangeable chunk's placements so consecutive MPI ranks
        # land topology-adjacent.  A pure post-fill permutation — the
        # fill plan's node multiset (and thus every capacity/feasibility
        # verdict) is untouched.
        ssn.rank_assign_fns.append(self._topo.assign_ranks)

    def extra_scores(self, tasks):
        """The preferred-level boosts, registered as the plugin's own
        method so the session's span carries the plugin's name."""
        return self._topo.extra_scores(tasks)
