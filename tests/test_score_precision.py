"""TPU score-key precision split: property-test the f32 key downcast
against the exact f64/u64 ordering ON CPU, via the simulated downcast
hook (`ops/allocate_grouped._score_keys(force_f32=True)` /
`allocate_grouped(f32_keys=True)`).

A TPU runs f32 score keys (XLA cannot lower a u64 bitcast there), and
comparing its placements with an x64 recompute needs a chip
(``chip_smoke.py`` compares the rungs on one).  These tests are the
tier-1 guardian that does not: they pin the two properties the parity
argument rests on —

1. the downcast is MONOTONE: f64→f32 rounding can collapse near-equal
   scores into one key (ties then break by node index) but can never
   invert a strict ordering;
2. on score distributions whose values are f32-exact (tier constants +
   coarse binpack terms — the shape real clusters overwhelmingly
   produce), the downcast keys order IDENTICALLY, so placements are
   bit-identical to the exact u64 path.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from kai_scheduler_tpu.ops.allocate_grouped import (_score_keys,
                                                    allocate_grouped)
from kai_scheduler_tpu.ops.scoring import (AVAILABILITY, MAX_HIGH_DENSITY,
                                           NOMINATED_NODE, RESOURCE_TYPE,
                                           TOPOLOGY)


def _keys(scores, force_f32):
    key, _, _ = _score_keys(jnp.asarray(scores, jnp.float64),
                            force_f32=force_f32)
    return np.asarray(key)


class TestKeyMonotonicity:
    """Property: for every pair a < b (f64), key32(a) <= key32(b)."""

    @pytest.mark.parametrize("seed", range(8))
    def test_random_score_mixtures(self, seed):
        rng = np.random.default_rng(seed)
        # Score-shaped values: tier constants + a continuous binpack
        # term + adversarial nudges at f32 rounding granularity.
        tiers = rng.choice(
            [0.0, RESOURCE_TYPE, AVAILABILITY, TOPOLOGY, NOMINATED_NODE],
            size=512)
        binpack = rng.random(512) * MAX_HIGH_DENSITY
        eps = rng.choice([0.0, 1e-7, -1e-7, 1e-4], size=512)
        scores = np.sort(tiers + binpack + eps)
        k32 = _keys(scores, force_f32=True)
        k64 = _keys(scores, force_f32=False)
        # Sorted ascending scores must yield non-decreasing keys in BOTH
        # precisions (monotone), and the u64 keys strictly increase
        # wherever the scores strictly increase.
        assert (np.diff(k32.astype(np.int64)) >= 0).all()
        strict = np.diff(scores) > 0
        assert (np.diff(k64.astype(object))[strict] > 0).all()

    def test_negative_and_sentinel_scores(self):
        from kai_scheduler_tpu.ops.allocate import NEG
        scores = np.array([NEG, -1e6, -1.5, -1e-9, 0.0, 1e-9, 1.5,
                           AVAILABILITY, NOMINATED_NODE + 9.0])
        k32 = _keys(scores, force_f32=True)
        k64 = _keys(scores, force_f32=False)
        assert (np.diff(k32.astype(np.int64)) >= 0).all()
        assert (np.diff(k64.astype(object)) > 0).all()

    def test_downcast_only_collapses_ties(self):
        """Scores that differ below f32 resolution collapse to ONE key
        (never invert): the fill then breaks the tie by node index,
        which is exactly the exact kernel's argmax tie-break."""
        base = 100.0 + 4.0  # availability tier + binpack
        scores = np.array([base, base + 1e-13, base + 1e-12])
        k32 = _keys(scores, force_f32=True)
        assert len(set(k32.tolist())) == 1
        k64 = _keys(scores, force_f32=False)
        assert len(set(k64.tolist())) == 3


class TestEndToEndDowncastParity:
    """allocate_grouped(f32_keys=True) vs the exact u64 path on f32-exact
    score distributions: identical placements, pipelined flags, success."""

    def _instance(self, seed, n_nodes=24, n_jobs=6):
        rng = np.random.default_rng(seed)
        alloc = np.tile([8000.0, 64e9, 8.0], (n_nodes, 1))
        idle = alloc.copy()
        # Integer GPU frees: the binpack term (free-min)/span stays a
        # small-denominator rational -> f32-exact orderings.
        idle[:, 2] -= rng.integers(0, 6, n_nodes)
        rel = np.zeros((n_nodes, 3))
        rel[:, 2] = rng.integers(0, 3, n_nodes)
        labels = np.full((n_nodes, 1), -1, np.int32)
        labels[: n_nodes // 2, 0] = 0
        taints = np.full((n_nodes, 1), -1, np.int32)
        room = np.full(n_nodes, 110.0)
        reqs, jobs, sels = [], [], []
        for j in range(n_jobs):
            gang = int(rng.integers(1, 5))
            gpu = float(rng.integers(1, 4))
            s = 0 if rng.random() < 0.3 else -1
            for _ in range(gang):
                reqs.append([1000.0, 1e9, gpu])
                jobs.append(j)
                sels.append(s)
        nodes = tuple(map(jnp.asarray,
                          (alloc, idle, rel, labels, taints, room)))
        return (nodes, np.array(reqs), np.array(jobs, np.int32),
                np.array(sels, np.int32)[:, None],
                np.full((len(reqs), 1), -1, np.int32),
                np.ones(n_jobs, bool))

    @pytest.mark.parametrize("seed", range(6))
    def test_placements_identical(self, seed):
        nodes, req, job, sel, tol, allowed = self._instance(seed)
        exact = allocate_grouped(nodes, req, job, sel, tol, allowed)
        down = allocate_grouped(nodes, req, job, sel, tol, allowed,
                                f32_keys=True)
        np.testing.assert_array_equal(np.asarray(exact.placements),
                                      np.asarray(down.placements))
        np.testing.assert_array_equal(np.asarray(exact.pipelined),
                                      np.asarray(down.pipelined))
        np.testing.assert_array_equal(np.asarray(exact.job_success),
                                      np.asarray(down.job_success))

    def test_sub_f32_tie_breaks_by_index_not_inversion(self):
        """An adversarial sub-f32 score split: the downcast path may
        permute WITHIN the collapsed tie class, but capacity totals and
        job success must match the exact path."""
        n = 8
        alloc = np.tile([8000.0, 64e9, 8.0], (n, 1))
        idle = alloc.copy()
        # Frees that differ at 1e-10 granularity: distinct in f64,
        # one tie class in f32.
        idle[:, 2] = 8.0 - np.arange(n) * 1e-10
        nodes = tuple(map(jnp.asarray, (
            alloc, idle, np.zeros((n, 3)),
            np.full((n, 1), -1, np.int32), np.full((n, 1), -1, np.int32),
            np.full(n, 110.0))))
        req = np.tile([1000.0, 1e9, 4.0], (6, 1))
        job = np.zeros(6, np.int32)
        sel = np.full((6, 1), -1, np.int32)
        tol = np.full((6, 1), -1, np.int32)
        allowed = np.ones(1, bool)
        exact = allocate_grouped(nodes, req, job, sel, tol, allowed)
        down = allocate_grouped(nodes, req, job, sel, tol, allowed,
                                f32_keys=True)
        assert bool(exact.job_success[0]) == bool(down.job_success[0])
        assert (np.asarray(exact.placements) >= 0).sum() == \
            (np.asarray(down.placements) >= 0).sum()
