"""minruntime plugin: protect young victims from preemption/reclaim.

Mirrors pkg/scheduler/plugins/minruntime/minruntime.go:78-205: victims whose
gangs started running less than the queue's (or global default) minimum
runtime ago are filtered out of preempt/reclaim victim sets, and scenarios
containing protected victims are rejected.
"""

from __future__ import annotations

from .base import Plugin, register_plugin


@register_plugin("minruntime")
class MinRuntimePlugin(Plugin):
    def __init__(self, args=None):
        super().__init__(args)
        self.default_preempt = float(self.args.get("preempt_min_runtime", 0)
                                     if args else 0)
        self.default_reclaim = float(self.args.get("reclaim_min_runtime", 0)
                                     if args else 0)

    def on_session_open(self, ssn) -> None:
        self.ssn = ssn
        ssn.preempt_victim_filters.append(self.filter_preempt)
        ssn.reclaim_victim_filters.append(self.filter_reclaim)
        ssn.preempt_scenario_validators.append(self.validate_preempt)
        ssn.reclaim_scenario_validators.append(self.validate_reclaim)

    def _protected(self, job, min_runtime: float) -> bool:
        if min_runtime <= 0 or job.last_start_ts is None:
            return False
        return (self.ssn.cluster.now - job.last_start_ts) < min_runtime

    def _min_runtime(self, queue_id: str, kind: str) -> float:
        q = self.ssn.cluster.queues.get(queue_id)
        # Queue-level override wins over the shard default (:148-205).
        while q is not None:
            val = (q.preempt_min_runtime if kind == "preempt"
                   else q.reclaim_min_runtime)
            if val is not None:
                return val
            q = self.ssn.cluster.queues.get(q.parent) if q.parent else None
        return self.default_preempt if kind == "preempt" \
            else self.default_reclaim

    def _unprotected(self, victims, kind: str):
        """The victims no minimum runtime protects, in their order, each
        judged alone.  The minimum depends on a victim's queue and the
        kind alone: it is looked up once a queue a call, and where none
        is positive nothing can be protected and the list itself goes
        back."""
        minimum = {qid: self._min_runtime(qid, kind)
                   for qid in {v.queue_id for v in victims}}
        if all(m <= 0 for m in minimum.values()):
            return victims
        return [v for v in victims
                if not self._protected(v, minimum[v.queue_id])]

    def filter_preempt(self, preemptor, victims):
        return self._unprotected(victims, "preempt")

    def filter_reclaim(self, reclaimer, victims):
        return self._unprotected(victims, "reclaim")

    def _none_protected(self, scenario, kind: str) -> bool:
        victims = [v for v, _ in scenario.victims]
        return len(self._unprotected(victims, kind)) == len(victims)

    def validate_preempt(self, scenario) -> bool:
        return self._none_protected(scenario, "preempt")

    def validate_reclaim(self, scenario) -> bool:
        return self._none_protected(scenario, "reclaim")
