"""Native state store: accounting parity with NodeInfo + checkpoint speed."""

import numpy as np
import pytest

from kai_scheduler_tpu.native import NativeNodeTable, native_available

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="no native toolchain")


class TestNativeStore:
    def test_accounting_matches_nodeinfo_rules(self):
        t = NativeNodeTable(2, 3)
        t.set_node(0, np.array([8000.0, 64e9, 8.0]), 110)
        t.set_node(1, np.array([8000.0, 64e9, 8.0]), 110)
        req = np.array([1000.0, 1e9, 2.0])

        t.add_task(0, req, status=0)  # allocated
        assert t.used[0, 2] == 2 and t.idle[0, 2] == 6
        t.add_task(0, req, status=1)  # releasing: used AND releasing
        assert t.used[0, 2] == 4 and t.releasing[0, 2] == 2
        t.add_task(1, req, status=2)  # pipelined claims releasing
        assert t.releasing[1, 2] == -2
        t.remove_task(0, req, status=0)
        assert t.used[0, 2] == 2
        assert t.room[0] == 109  # two adds, one remove

    def test_checkpoint_rollback(self):
        t = NativeNodeTable(1, 3)
        t.set_node(0, np.array([8000.0, 64e9, 8.0]), 110)
        req = np.array([0.0, 0.0, 4.0])
        cp = t.checkpoint()
        t.add_task(0, req, status=0)
        assert t.idle[0, 2] == 4
        t.rollback(cp)
        assert t.idle[0, 2] == 8
        assert t.room[0] == 110

    def test_views_are_zero_copy(self):
        t = NativeNodeTable(4, 3)
        for i in range(4):
            t.set_node(i, np.array([1.0, 1.0, 1.0]), 10)
        v1 = t.used
        t.add_task(2, np.array([0.5, 0.0, 0.0]), status=0)
        # Same buffer: the earlier view reflects the mutation.
        assert v1[2, 0] == 0.5

    def test_bulk_load(self):
        t = NativeNodeTable(3, 3)
        alloc = np.arange(9, dtype=np.float64).reshape(3, 3)
        used = np.ones((3, 3))
        rel = np.zeros((3, 3))
        room = np.full(3, 5.0)
        t.bulk_load(alloc, used, rel, room)
        np.testing.assert_array_equal(t.allocatable, alloc)
        np.testing.assert_array_equal(t.idle, alloc - used)

    def test_scale_smoke(self):
        """100k nodes: creation + 10k ops + checkpoint stay fast."""
        import time
        n = 100_000
        t = NativeNodeTable(n, 3)
        alloc = np.tile([64000.0, 512e9, 8.0], (n, 1))
        t.bulk_load(alloc, np.zeros((n, 3)), np.zeros((n, 3)),
                    np.full(n, 110.0))
        req = np.array([1000.0, 1e9, 1.0])
        t0 = time.perf_counter()
        for i in range(10_000):
            t.add_task(i % n, req, status=0)
        ops_s = 10_000 / (time.perf_counter() - t0)
        t0 = time.perf_counter()
        cp = t.checkpoint()
        t.rollback(cp)
        cp_ms = (time.perf_counter() - t0) * 1000
        assert ops_s > 20_000  # ctypes-bound but plenty for a cycle
        assert cp_ms < 1000    # full-table checkpoint+rollback (smoke, not
        #                        a benchmark: generous bound for CI load)
        # Rollback restores the post-add state the checkpoint captured.
        assert t.idle[0, 2] == 7.0


def test_a_view_keeps_the_c_table_alive():
    """``NodeInfo.used`` stays a view of a session's table after the
    session is gone: the C memory must outlive the table object for as
    long as any view of it is held."""
    import gc
    import weakref
    t = NativeNodeTable(3, 3)
    for i in range(3):
        t.set_node(i, np.array([8000.0, 64e9, 8.0]), 110)
    t.add_task(1, np.array([1000.0, 1e9, 2.0]), status=0)
    store = weakref.ref(t._store)
    row = t.used[1]
    del t
    gc.collect()
    assert store() is not None
    assert row.tolist() == [1000.0, 1e9, 2.0]
    row += 1.0
    assert row[2] == 3.0
    del row
    gc.collect()
    assert store() is None
