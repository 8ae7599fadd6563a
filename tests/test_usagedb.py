"""Decay-math property ring for utils/usagedb.py + prometheus_usage.py.

The tensor-backed usage store's contract (DESIGN §13): half-life
exactness of the decayed fold, kernel/numpy parity to 1 ulp, the sliding
window cap, checkpoint-log restart restore (commit-log pattern, torn
tails included), and the staleness -> proportion-degraded transition.
"""

import os

import numpy as np
import pytest

from kai_scheduler_tpu.ops.usage import usage_decay_kernel, usage_decay_np
from kai_scheduler_tpu.utils.usagedb import (InMemoryUsageDB, UsageParams,
                                             UsageSnapshot,
                                             resolve_usage_client)

pytestmark = pytest.mark.chaos

SEED_BASE = int(os.environ.get("KAI_FAULT_SEED", "0")) * 1000
R = 3


def vec(gpu=0.0, cpu=0.0, mem=0.0):
    return np.array([cpu, mem, gpu], float)


class TestDecayKernelParity:
    def test_kernel_within_one_ulp_of_numpy(self):
        """Same expression, but XLA may contract ``usage * decay +
        alloc`` into one fused multiply-add (one rounding where numpy
        takes two): the results agree to 1 ulp of the test's dtype, not
        bit for bit."""
        rng = np.random.default_rng(SEED_BASE + 1)
        for _ in range(20):
            q = int(rng.integers(1, 64))
            usage = rng.uniform(0, 100, (q, R))
            alloc = rng.uniform(0, 10, (q, R))
            keep = rng.uniform(size=q) < 0.8
            decay = float(rng.uniform(0.1, 1.0))
            got = np.asarray(usage_decay_kernel(usage, alloc, keep,
                                                decay))
            want = usage_decay_np(usage, alloc, keep, decay)
            assert got.dtype == want.dtype
            assert np.all(np.abs(got - want) <= np.spacing(np.abs(want)))


class TestHalfLife:
    def params(self, hl=600.0, window=1e9):
        return UsageParams(half_life_period_seconds=hl,
                           window_size_seconds=window)

    def test_half_life_exactness(self):
        """One sample, then a zero sample exactly one half-life later:
        the standing average is (v * 0.5) / (0.5 + 1) — the 0.5 factor
        is exact, not approximate."""
        db = InMemoryUsageDB(self.params())
        db.record(0.0, "q", vec(gpu=2.0))
        assert db.queue_usage(0.0)["q"][2] == 2.0
        db.record(600.0, "q", vec(gpu=0.0))
        got = db.queue_usage(600.0)["q"][2]
        assert got == (2.0 * 0.5) / (0.5 + 1.0)

    def test_decay_invariant_between_samples(self):
        """With no new samples the weighted AVERAGE holds steady (the
        integral and the weight decay by the same factor)."""
        db = InMemoryUsageDB(self.params())
        db.record(0.0, "q", vec(gpu=4.0))
        first = db.queue_usage(0.0)["q"].copy()
        later = db.queue_usage(500.0)["q"]
        assert np.array_equal(first, later)

    def test_flat_mode_without_half_life(self):
        db = InMemoryUsageDB(self.params(hl=None))
        db.record(0.0, "q", vec(gpu=2.0))
        db.record(1000.0, "q", vec(gpu=4.0))
        assert db.queue_usage(1000.0)["q"][2] == 3.0  # plain average

    def test_capacity_normalization(self):
        db = InMemoryUsageDB(self.params(),
                             cluster_capacity=vec(gpu=8.0, cpu=1.0,
                                                  mem=1.0))
        db.record(0.0, "q", vec(gpu=4.0))
        assert db.queue_usage(0.0)["q"][2] == 0.5

    def test_single_dispatch_per_cycle(self):
        from kai_scheduler_tpu.utils.metrics import METRICS
        db = InMemoryUsageDB(self.params())
        before = METRICS.counters.get("usage_decay_dispatch_total", 0)
        for cycle in range(5):
            db.record_cycle(float(cycle * 60), {
                f"q{i}": vec(gpu=float(i)) for i in range(40)})
        after = METRICS.counters.get("usage_decay_dispatch_total", 0)
        assert after - before == 5  # one fold per cycle, never per queue


class TestWindowCap:
    def test_queue_outside_window_reads_zero(self):
        db = InMemoryUsageDB(UsageParams(half_life_period_seconds=None,
                                         window_size_seconds=100.0))
        db.record(0.0, "old", vec(gpu=8.0))
        db.queue_usage(0.0)
        out = db.queue_usage(200.0)
        assert np.all(out["old"] == 0.0)

    def test_expired_integral_restarts_from_zero(self):
        """A fresh sample after the window must not resurrect decayed
        history — the keep mask zeroes the stale integral in-kernel."""
        db = InMemoryUsageDB(UsageParams(half_life_period_seconds=None,
                                         window_size_seconds=100.0))
        db.record(0.0, "q", vec(gpu=8.0))
        db.queue_usage(0.0)
        db.record(500.0, "q", vec(gpu=2.0))
        out = db.queue_usage(500.0)
        # weight carries both samples but the old integral was dropped.
        assert out["q"][2] == 2.0 / 2.0

    def test_tumbling_window_reset(self):
        db = InMemoryUsageDB(UsageParams(half_life_period_seconds=None,
                                         window_size_seconds=100.0,
                                         window_type="tumbling"))
        db.record(90.0, "q", vec(gpu=8.0))
        db.queue_usage(90.0)
        out = db.queue_usage(150.0)  # next tumble: [100, 200)
        assert np.all(out["q"] == 0.0)


class TestRestartRestore:
    def test_checkpoint_roundtrip(self, tmp_path):
        path = str(tmp_path / "usage.log")
        db = InMemoryUsageDB(UsageParams(half_life_period_seconds=600.0))
        db.attach_log(path, fsync=False)
        for cycle in range(4):
            db.record_cycle(cycle * 60.0, {"a": vec(gpu=4.0),
                                           "b": vec(gpu=1.0)})
        want = db.queue_usage(240.0)

        db2 = InMemoryUsageDB(UsageParams(half_life_period_seconds=600.0))
        assert db2.attach_log(path, fsync=False)
        got = db2.queue_usage(240.0)
        assert set(got) == set(want)
        for q in want:
            assert np.array_equal(got[q], want[q])
        assert db2.last_record_ts == db.last_record_ts

    def test_capacity_normalizer_survives_restart(self, tmp_path):
        """The checkpoint carries cluster_capacity: a restart within
        the staleness budget must serve NORMALIZED usage on its very
        first fetch — before any cycle refreshes the normalizer — or
        raw units would zero every queue's over-quota share."""
        path = str(tmp_path / "usage.log")
        db = InMemoryUsageDB(UsageParams())
        db.attach_log(path, fsync=False)
        db.cluster_capacity = vec(gpu=8.0, cpu=1.0, mem=1.0)
        db.record_cycle(0.0, {"q": vec(gpu=4.0)})
        db2 = InMemoryUsageDB(UsageParams())
        assert db2.attach_log(path, fsync=False)
        assert db2.queue_usage(60.0)["q"][2] == 0.5  # normalized

    def test_torn_tail_falls_back_to_previous_checkpoint(self, tmp_path):
        path = str(tmp_path / "usage.log")
        db = InMemoryUsageDB(UsageParams())
        db.attach_log(path, fsync=False)
        db.record_cycle(0.0, {"a": vec(gpu=2.0)})
        db.record_cycle(60.0, {"a": vec(gpu=2.0)})
        with open(path, "ab") as f:
            f.write(b"deadbeef {torn-json\n")
        db2 = InMemoryUsageDB(UsageParams())
        assert db2.attach_log(path, fsync=False)
        assert db2.queue_usage(60.0)["a"][2] == 2.0

    def test_compaction_keeps_latest_state(self, tmp_path):
        path = str(tmp_path / "usage.log")
        db = InMemoryUsageDB(UsageParams())
        db.attach_log(path, fsync=False)
        db._log.compact_every = 3
        for cycle in range(7):
            db.record_cycle(cycle * 60.0, {"a": vec(gpu=float(cycle))})
        size = os.path.getsize(path)
        assert size < 4096  # compacted, not an unbounded append log
        db2 = InMemoryUsageDB(UsageParams())
        db2.attach_log(path, fsync=False)
        assert np.array_equal(db2.queue_usage(360.0)["a"],
                              db.queue_usage(360.0)["a"])


class TestStaleness:
    def test_is_stale_tracks_record_not_fetch(self):
        db = InMemoryUsageDB(UsageParams(staleness_period_seconds=100.0))
        db.record_cycle(0.0, {"q": vec(gpu=1.0)})
        assert not db.is_stale(50.0)
        # Fetching must NOT refresh staleness (the old fetch-based check
        # could never trip for the in-memory store).
        db.queue_usage(150.0)
        assert db.is_stale(150.0)
        assert db.queue_usage(150.0).stale

    def test_never_recorded_is_not_stale(self):
        db = InMemoryUsageDB(UsageParams(staleness_period_seconds=100.0))
        assert not db.is_stale(1e9)
        assert not db.queue_usage(1e9).stale

    def test_stale_snapshot_trips_proportion_degraded_mode(self):
        """Stale usage => the documented degraded mode: usage ignored
        (fair shares equal the no-usage division) and
        ``usage_stale_cycles_total`` counts the cycle."""
        from kai_scheduler_tpu.utils import cluster_spec as cs
        from kai_scheduler_tpu.utils.metrics import METRICS

        def spec(usage):
            return {
                "nodes": {"n0": {"gpu": 8}},
                "queues": {"a": {"deserved": {"gpu": 1}},
                           "b": {"deserved": {"gpu": 1}}},
                "jobs": {"ja": {"queue": "a",
                                "tasks": [{"gpu": 2}] * 3},
                         "jb": {"queue": "b",
                                "tasks": [{"gpu": 2}] * 3}},
                "queue_usage": usage,
            }

        stale = UsageSnapshot({"a": vec(gpu=1.0)})
        stale.stale = True
        before = METRICS.counters.get("usage_stale_cycles_total", 0)
        ssn_stale = cs.build_session(spec(stale))
        after = METRICS.counters.get("usage_stale_cycles_total", 0)
        assert after == before + 1
        ssn_none = cs.build_session(spec(None))
        for qid in ("a", "b"):
            assert np.array_equal(
                ssn_stale.proportion.queues[qid].fair_share,
                ssn_none.proportion.queues[qid].fair_share)
            assert np.all(ssn_stale.proportion.queues[qid].usage == 0)

        # The same snapshot NOT marked stale must shift shares.
        fresh = UsageSnapshot({"a": vec(gpu=1.0)})
        ssn_fresh = cs.build_session(spec(fresh))
        assert not np.array_equal(
            ssn_fresh.proportion.queues["a"].fair_share,
            ssn_none.proportion.queues["a"].fair_share)

    def test_empty_stale_snapshot_keeps_its_flag_through_session(self):
        """An EMPTY snapshot can still be stale (total scrape outage
        from startup — the most degraded case); the session must not
        swallow the flag via an `or {}` default."""
        from kai_scheduler_tpu.utils import cluster_spec as cs
        from kai_scheduler_tpu.utils.metrics import METRICS
        empty_stale = UsageSnapshot()
        empty_stale.stale = True
        before = METRICS.counters.get("usage_stale_cycles_total", 0)
        ssn = cs.build_session({
            "nodes": {"n0": {"gpu": 8}},
            "queues": {"a": {}},
            "jobs": {"j": {"queue": "a", "tasks": [{"gpu": 1}]}},
            "queue_usage": empty_stale,
        })
        assert getattr(ssn.queue_usage, "stale", False)
        assert METRICS.counters.get("usage_stale_cycles_total",
                                    0) == before + 1

    def test_prometheus_snapshot_carries_stale_flag(self):
        from kai_scheduler_tpu.utils.prometheus_usage import \
            PrometheusUsageClient
        client = PrometheusUsageClient(
            "http://127.0.0.1:1",  # nothing listens: fetch fails
            UsageParams(staleness_period_seconds=10.0))
        snap = client.queue_usage(1000.0)
        assert isinstance(snap, UsageSnapshot)
        assert snap.stale and snap == {}


class TestResolver:
    def test_memory_scheme(self):
        assert isinstance(resolve_usage_client("memory://"),
                          InMemoryUsageDB)

    def test_unknown_scheme_disables(self):
        assert resolve_usage_client("bogus://x") is None


class TestCorruptRestore:
    """Satellite (PR 15): torn-tail and CRC-mismatch restores enter the
    documented stale->degraded mode LOUDLY — ``usage_log_corrupt_total``
    fires and every fetch reads stale (the proportion plugin then
    ignores usage + counts ``usage_stale_cycles_total``) until a FRESH
    sample folds.  Salvaged history of unknown age must never silently
    drive the fairness penalty."""

    def _metric(self, name):
        from kai_scheduler_tpu.utils.metrics import METRICS
        return METRICS.counters.get(name, 0)

    def test_torn_tail_restore_is_loud_and_degraded(self, tmp_path):
        path = str(tmp_path / "usage.log")
        db = InMemoryUsageDB(UsageParams())
        db.attach_log(path, fsync=False)
        db.record_cycle(0.0, {"a": vec(gpu=2.0)})
        with open(path, "ab") as f:
            f.write(b"deadbeef {torn-json\n")
        corrupt0 = self._metric("usage_log_corrupt_total")
        db2 = InMemoryUsageDB(UsageParams())
        assert db2.attach_log(path, fsync=False)  # prefix restored...
        assert self._metric("usage_log_corrupt_total") == corrupt0 + 1
        snap = db2.queue_usage(1.0)   # ...well inside the staleness
        assert snap.stale, \
            "corrupt restore served as fresh (degraded mode not taken)"

    def test_crc_mismatch_mid_file_falls_back_loud(self, tmp_path):
        """Bit rot INSIDE the file (CRC mismatch on a fully-formed
        line): everything after it is untrusted — restore the prefix,
        fire the metric, read stale."""
        path = str(tmp_path / "usage.log")
        db = InMemoryUsageDB(UsageParams())
        db.attach_log(path, fsync=False)
        db.record_cycle(0.0, {"a": vec(gpu=2.0)})
        db.record_cycle(60.0, {"a": vec(gpu=6.0)})
        with open(path, "rb") as f:
            lines = f.readlines()
        assert len(lines) == 2
        rotted = bytearray(lines[1])
        rotted[len(rotted) // 2] ^= 0xFF   # flip one payload bit
        with open(path, "wb") as f:
            f.write(lines[0] + bytes(rotted))
        corrupt0 = self._metric("usage_log_corrupt_total")
        db2 = InMemoryUsageDB(UsageParams())
        assert db2.attach_log(path, fsync=False)
        assert self._metric("usage_log_corrupt_total") == corrupt0 + 1
        # The prefix (first checkpoint) is what survived.
        assert db2.queue_usage(30.0)["a"][2] == 2.0
        assert db2.queue_usage(30.0).stale

    def test_fully_corrupt_log_restores_nothing_but_is_loud(
            self, tmp_path):
        path = str(tmp_path / "usage.log")
        with open(path, "wb") as f:
            f.write(b"not a checkpoint at all\n")
        corrupt0 = self._metric("usage_log_corrupt_total")
        db = InMemoryUsageDB(UsageParams())
        assert not db.attach_log(path, fsync=False)
        assert self._metric("usage_log_corrupt_total") == corrupt0 + 1
        assert db.is_stale(0.0), "untrusted restore must read degraded"

    def test_fresh_sample_ends_the_degradation(self, tmp_path):
        path = str(tmp_path / "usage.log")
        db = InMemoryUsageDB(UsageParams())
        db.attach_log(path, fsync=False)
        db.record_cycle(0.0, {"a": vec(gpu=2.0)})
        with open(path, "ab") as f:
            f.write(b"deadbeef {torn\n")
        db2 = InMemoryUsageDB(UsageParams())
        db2.attach_log(path, fsync=False)
        assert db2.queue_usage(1.0).stale
        db2.record_cycle(2.0, {"a": vec(gpu=1.0)})   # trustworthy data
        assert not db2.queue_usage(3.0).stale, \
            "degradation must end when fresh samples fold"

    def test_proportion_degraded_mode_via_stale_snapshot(self, tmp_path):
        """End to end into the plugin contract: the corrupt-restore
        snapshot drives the proportion plugin's degraded path (usage
        zeroed + usage_stale_cycles_total) exactly like outage
        staleness does."""
        path = str(tmp_path / "usage.log")
        db = InMemoryUsageDB(UsageParams())
        db.attach_log(path, fsync=False)
        db.record_cycle(0.0, {"a": vec(gpu=8.0)})
        with open(path, "ab") as f:
            f.write(b"deadbeef {torn\n")
        db2 = InMemoryUsageDB(UsageParams())
        db2.attach_log(path, fsync=False)
        snap = db2.queue_usage(1.0)
        assert snap.stale and snap  # stale AND non-empty: the worst mix
