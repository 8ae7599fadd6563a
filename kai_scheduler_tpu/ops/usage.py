"""Per-queue historical-usage decay: ONE tensor update per cycle.

The time-aware fairness subsystem (utils/usagedb.py, DESIGN §13) keeps
the whole fleet's historical usage as a single ``[Q, R]`` decayed
integral.  Each cycle folds in that cycle's allocation sample with the
half-life factor applied to everything older:

    usage' = where(keep, usage * decay, 0) + alloc

where ``decay = 0.5^(dt / half_life)`` for the elapsed time since the
previous fold and ``keep`` masks queues whose last sample still lies
inside the sliding window (a queue that fell out of the window restarts
from zero — the tensor analog of the sample-deque popleft).

This replaces the per-queue host loop the original ``InMemoryUsageDB``
stub paid (O(queues x samples) Python per fetch) with one jitted
dispatch per cycle — the queue-forest kernel's argument (DESIGN §2b)
applied to the usage axis.  ``tools/fleet_budget.py`` pins the dispatch
count structurally: a silent fall-back to a per-queue loop multiplies
``usage_decay_dispatch_total`` by Q and trips the gate.

``usage_decay_np`` is the host reference: the same elementwise
expression, asserted equal to 1 ulp in tests/test_usagedb.py — XLA may
contract the multiply-add into one FMA (one rounding instead of numpy's
two), so the two are not bit-identical.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def usage_decay_kernel(usage, alloc, keep, decay):
    """One decayed fold: [Q,R] usage, [Q,R] alloc sample, [Q] bool keep
    (inside-window mask), scalar decay factor."""
    return jnp.where(keep[:, None], usage * decay, 0.0) + alloc


def usage_decay_np(usage: np.ndarray, alloc: np.ndarray,
                   keep: np.ndarray, decay: float) -> np.ndarray:
    """Host reference — formula-identical to the kernel."""
    return np.where(keep[:, None], usage * decay, 0.0) + alloc
