"""Plain reference of what one cycle of cross-queue reclaim may do.

Written from the guarantees the configuration states (KAI-Scheduler's
reclaim action: a job of a queue under its fair share takes resources from
preemptible jobs of OTHER queues; a gang is whole or not at all, the
reclaimer's and every victim's), in numpy and plain Python.  It imports
nothing of ``kai_scheduler_tpu`` and takes nothing the program has made:
its inputs are the client's ledger and its book of the jobs it submitted.
Every answer is exact, so every limit is 0.
"""

from __future__ import annotations

import numpy as np

EPS = 1e-9


def nodes_over_capacity(capacity, used, pods, max_pods) -> int:
    """Nodes past their cpu, memory, GPUs or pod room."""
    over = np.any(used > capacity + EPS, axis=1) | (pods > max_pods)
    return int(over.sum())


def victim_faults(victims, reclaimer_queue) -> dict:
    """``victims``: [(queue, preemptible)] of every pod a cycle evicted.
    A victim has to be preemptible and of another queue than the
    reclaimer's; with no reclaimer pending nothing may be evicted."""
    return {
        "victims_not_preemptible": sum(1 for _q, p in victims if not p),
        "victims_from_own_queue": sum(
            1 for q, _p in victims if q == reclaimer_queue),
        "evictions_without_reclaimer":
            len(victims) if reclaimer_queue is None else 0}


def gangs_left_below_minimum(running, evicted, minimum) -> int:
    """Jobs that a cycle's evictions left with some pods running but
    fewer than their gang's minimum: below it a victim goes whole.
    ``running``, ``evicted``, ``minimum``: job -> count of pods."""
    left = 0
    for job, gone in evicted.items():
        rest = running[job] - gone
        left += 0 < rest < minimum[job]
    return left


def gang_faults(bound: int, size: int) -> dict:
    """A gang binds all its pods in one cycle or none."""
    return {"gangs_partly_bound": int(0 < bound < size)}
