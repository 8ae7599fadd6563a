"""Scheduler configuration: actions sequence + plugin tiers with args.

Mirrors the reference's scheduler config YAML and embedded default
(pkg/scheduler/conf, conf_util/scheduler_conf_util.go:36-61): an ordered
actions string and plugin tiers, each plugin with an optional string-map of
arguments, plus global knobs (kValue for usage-penalized fair share,
staleness grace, queue depth per action).
"""

from __future__ import annotations

from dataclasses import dataclass, field


DEFAULT_PLUGINS = [
    "predicates", "proportion", "priority", "nodeplacement", "elastic",
    "taskorder", "subgrouporder", "nodeavailability", "resourcetype",
    "gpupack", "gpusharingorder", "nominatednode", "podaffinity",
    "minruntime", "dynamicresources", "topology", "snapshot",
]

DEFAULT_ACTIONS = ["allocate", "consolidation", "reclaim", "preempt",
                   "stalegangeviction"]


@dataclass
class PluginConfig:
    name: str
    args: dict = field(default_factory=dict)


@dataclass
class SchedulerConfig:
    actions: list = field(default_factory=lambda: list(DEFAULT_ACTIONS))
    plugins: list = field(default_factory=lambda: [
        PluginConfig(p) for p in DEFAULT_PLUGINS])
    # Usage-penalty coefficient k in w' = max(0, W' + k*(W' - U'))
    # (resource_division.go:245).
    k_value: float = 1.0
    # Placement strategies per resource type (nodeplacement args).
    gpu_placement_strategy: str = "binpack"
    cpu_placement_strategy: str = "binpack"
    # Gang staleness grace before eviction (stalegangeviction action).
    default_staleness_grace_seconds: float = 60.0
    # Max jobs considered per queue per action (queue depth).
    queue_depth_per_action: dict = field(default_factory=dict)
    # Reclaim saturation multiplier (reclaimable.go New).
    saturation_multiplier: float = 1.0
    # Scenario-simulation bounds (worst-case cycle latency control; the
    # metric scenarios_simulation_by_action tracks actual usage).
    max_scenarios_per_job: int = 16
    max_victims_considered: int = 32
    # Batched scenario pre-screen: score up to this many victim prefixes
    # in ONE device call (ops/scenario_batch.py); 0 disables.  Engages
    # lazily, only after ``scenario_prescreen_after`` simulated scenarios
    # failed — on the happy path (first scenario fits) it would be pure
    # overhead.
    scenario_prescreen_max: int = 256
    scenario_prescreen_after: int = 1
    # Confirm scenario solutions (pending job + victim re-placements) in
    # ONE multi-job kernel call instead of one device call per job.
    batched_scenario_confirm: bool = True
    # Scheduling-signature dedup of provably unschedulable jobs.
    use_scheduling_signatures: bool = True
    # Node-axis padding bucket to stabilize kernel shapes across cycles.
    node_pad_bucket: int = 0
    # Multi-chip: shard the node axis of the bulk-allocation kernel over
    # this many devices (0 = single chip).  The node axis pads to a mesh
    # multiple automatically.
    mesh_devices: int = 0
    # Bulk allocation: when at least this many plain jobs are pending,
    # the allocate action places them all through ONE kernel call per
    # round (job order fixed per round) instead of one call per job.
    # 0 disables bulk mode.
    bulk_allocation_threshold: int = 32
    # Rank-aware gang placement (ops/rankplace.py): permute
    # interchangeable gang members so consecutive MPI ranks land
    # topology-adjacent.  Pure post-fill permutation — placements'
    # node multiset is untouched; False keeps the rank-oblivious
    # assignment (the scale ring's A/B baseline).
    rank_aware_placement: bool = True
    # Whole-cycle deadline in seconds (0 disables).  Enforced by the
    # cycle driver between actions AND inside them at kernel-dispatch
    # granularity (Session.dispatch_kernel): past the deadline the cycle
    # aborts, uncommitted statements roll back, and the daemon moves on
    # to the next cycle — a mid-cycle device death degrades, never wedges.
    cycle_deadline_s: float = 0.0
    # Feature-gate overrides (pkg/common/feature_gates analog): gate name
    # -> bool.  Consulted at plugin registration (plugins/base.py) via
    # utils.feature_gates.FeatureGates; unset gates use KNOWN_GATES
    # defaults or API auto-detection (DRA discovery).
    feature_gates: dict = field(default_factory=dict)
    # Auto-detected gate values (e.g. DRA discovery against the live API
    # server): a separate layer under the explicit overrides above, so
    # re-detection on a fleet rebuild can still change the answer.
    detected_gates: dict = field(default_factory=dict)

    def gates(self, api=None):
        from ..utils.feature_gates import gates_for
        return gates_for(self, api)

    def plugin_args(self, name: str) -> dict:
        for p in self.plugins:
            if p.name == name:
                return p.args
        return {}

    def has_plugin(self, name: str) -> bool:
        return any(p.name == name for p in self.plugins)

    @classmethod
    def from_dict(cls, d: dict) -> "SchedulerConfig":
        """Build from the scheduler-config document shape the reference
        embeds (conf_util/scheduler_conf_util.go:36-61): an ``actions``
        string plus plugin tiers with optional argument maps."""
        return cls().apply_dict(d)

    def apply_dict(self, d: dict) -> "SchedulerConfig":
        """Apply a (partial) config document on top of this config: only
        keys present in ``d`` change; ``feature_gates`` merges.  The
        operator uses this to layer Config-CRD global args and per-shard
        SchedulingShard args over a shard's base config."""
        config = self
        if "actions" in d:
            actions = d["actions"]
            if isinstance(actions, str):
                actions = [a.strip() for a in actions.split(",")]
            config.actions = list(actions)
        tiers = d.get("tiers") or []
        plugins = []
        for tier in tiers:
            for p in tier.get("plugins", []):
                if isinstance(p, str):
                    plugins.append(PluginConfig(p))
                else:
                    plugins.append(PluginConfig(p["name"],
                                                p.get("arguments", {})))
        if plugins:
            config.plugins = plugins
        for key in ("k_value", "gpu_placement_strategy",
                    "cpu_placement_strategy",
                    "default_staleness_grace_seconds",
                    "saturation_multiplier", "use_scheduling_signatures",
                    "node_pad_bucket", "bulk_allocation_threshold",
                    "max_scenarios_per_job", "max_victims_considered",
                    "scenario_prescreen_max", "scenario_prescreen_after",
                    "batched_scenario_confirm", "cycle_deadline_s",
                    "rank_aware_placement"):
            if key in d:
                setattr(config, key, d[key])
        if "queue_depth_per_action" in d:
            config.queue_depth_per_action = dict(d["queue_depth_per_action"])
        gates = d.get("feature_gates", d.get("featureGates"))
        if gates:
            if isinstance(gates, str):
                from ..utils.feature_gates import parse_gate_string
                gates = parse_gate_string(gates)
            config.feature_gates = dict(config.feature_gates)
            config.feature_gates.update(
                {k: bool(v) for k, v in gates.items()})
        return config

    @classmethod
    def from_file(cls, path: str) -> "SchedulerConfig":
        """Load a YAML (or JSON) scheduler config document."""
        import yaml
        with open(path) as f:
            return cls.from_dict(yaml.safe_load(f) or {})
