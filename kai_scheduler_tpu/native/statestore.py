"""ctypes wrapper: NativeNodeTable with zero-copy numpy views.

The Session's dense node mirrors (framework/session.py) can be backed by
this table: statement ops become O(1) native calls, checkpoint/rollback of
the whole table is a native memcpy, and the arrays the device kernels
consume are views over the C buffers (no per-cycle Python packing loop).
"""

from __future__ import annotations

import ctypes

import numpy as np

from .build import load_statestore_lib

STATUS_ALLOCATED = 0
STATUS_RELEASING = 1
STATUS_PIPELINED = 2


def native_available() -> bool:
    return load_statestore_lib() is not None


def _as_dptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


class _Store:
    """The C table.  Freed when its last holder lets go, and every view of
    its buffers is a holder: ``NodeInfo.used`` stays a view of a session's
    table after that session is gone, until the next one re-binds it."""

    def __init__(self, lib, n_nodes: int, n_res: int):
        self._lib = lib
        self.handle = ctypes.c_void_p(lib.ss_create(n_nodes, n_res))

    def __del__(self):
        if self.handle:
            self._lib.ss_destroy(self.handle)


class NativeNodeTable:
    def __init__(self, n_nodes: int, n_res: int):
        self._lib = load_statestore_lib()
        if self._lib is None:
            raise RuntimeError("native toolchain unavailable")
        self.n_nodes = n_nodes
        self.n_res = n_res
        self._store = _Store(self._lib, n_nodes, n_res)
        self._handle = self._store.handle
        self._checkpoints: list = []
        self._views: dict = {}

    def __del__(self):
        lib = getattr(self, "_lib", None)
        if lib is not None:
            for cp in getattr(self, "_checkpoints", ()):
                lib.ss_destroy(cp)

    # -- loading -----------------------------------------------------------
    def set_node(self, i: int, allocatable: np.ndarray,
                 max_pods: float) -> None:
        a = np.ascontiguousarray(allocatable, np.float64)
        self._lib.ss_set_node(self._handle, i, _as_dptr(a), max_pods)

    def bulk_load(self, allocatable, used, releasing, room) -> None:
        a = np.ascontiguousarray(allocatable, np.float64)
        u = np.ascontiguousarray(used, np.float64)
        r = np.ascontiguousarray(releasing, np.float64)
        m = np.ascontiguousarray(room, np.float64)
        self._lib.ss_bulk_load(self._handle, _as_dptr(a), _as_dptr(u),
                               _as_dptr(r), _as_dptr(m))

    # -- accounting --------------------------------------------------------
    def add_task(self, node_idx: int, req: np.ndarray, status: int) -> None:
        r = np.ascontiguousarray(req, np.float64)
        self._lib.ss_add_task(self._handle, node_idx, _as_dptr(r), status)

    def remove_task(self, node_idx: int, req: np.ndarray,
                    status: int) -> None:
        r = np.ascontiguousarray(req, np.float64)
        self._lib.ss_remove_task(self._handle, node_idx, _as_dptr(r),
                                 status)

    # Batched forms: one ctypes round trip for a whole gang's placements
    # (the per-call overhead dominated bulk Statement application).
    def add_tasks(self, idx: np.ndarray, reqs: np.ndarray,
                  statuses: np.ndarray) -> None:
        i = np.ascontiguousarray(idx, np.int64)
        r = np.ascontiguousarray(reqs, np.float64)
        s = np.ascontiguousarray(statuses, np.int32)
        self._lib.ss_add_tasks(
            self._handle, len(i),
            i.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), _as_dptr(r),
            s.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))

    def remove_tasks(self, idx: np.ndarray, reqs: np.ndarray,
                     statuses: np.ndarray) -> None:
        i = np.ascontiguousarray(idx, np.int64)
        r = np.ascontiguousarray(reqs, np.float64)
        s = np.ascontiguousarray(statuses, np.int32)
        self._lib.ss_remove_tasks(
            self._handle, len(i),
            i.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), _as_dptr(r),
            s.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))

    # -- views (zero-copy over the C buffers) ------------------------------
    # The C buffers live at fixed addresses for the table's lifetime, so
    # each view is built once and cached — view construction showed up as
    # ~25% of per-task statement cost at 100k-node scale.
    def _view(self, ptr, shape):
        size = int(np.prod(shape))
        if size == 0:
            # A table of no nodes (the embedded daemon before any Node
            # exists) has no buffer: its C vectors' data() is NULL.
            return np.zeros(shape)
        buf = ctypes.cast(
            ptr, ctypes.POINTER(ctypes.c_double * size)).contents
        # The array's base is ``buf``, and every slice of the array keeps
        # that base: through it each view holds the C table alive.
        buf._store = self._store
        return np.frombuffer(buf, np.float64).reshape(shape)

    def _cached_view(self, name: str, fn_name: str, shape):
        view = self._views.get(name)
        if view is None:
            ptr = getattr(self._lib, fn_name)(self._handle)
            view = self._views[name] = self._view(ptr, shape)
        return view

    @property
    def idle(self) -> np.ndarray:
        # ss_idle refreshes the derived idle table in place; the buffer
        # address is stable so the cached view stays valid.
        self._lib.ss_idle(self._handle)
        return self._cached_view("idle", "ss_idle",
                                 (self.n_nodes, self.n_res))

    @property
    def allocatable(self) -> np.ndarray:
        return self._cached_view("allocatable", "ss_allocatable",
                                 (self.n_nodes, self.n_res))

    @property
    def used(self) -> np.ndarray:
        return self._cached_view("used", "ss_used",
                                 (self.n_nodes, self.n_res))

    @property
    def releasing(self) -> np.ndarray:
        return self._cached_view("releasing", "ss_releasing",
                                 (self.n_nodes, self.n_res))

    @property
    def room(self) -> np.ndarray:
        return self._cached_view("room", "ss_room", (self.n_nodes,))

    # -- checkpoint / rollback (native memcpy) -----------------------------
    def checkpoint(self) -> int:
        cp = ctypes.c_void_p(self._lib.ss_clone(self._handle))
        self._checkpoints.append(cp)
        return len(self._checkpoints) - 1

    def rollback(self, checkpoint_id: int) -> None:
        cp = self._checkpoints[checkpoint_id]
        self._lib.ss_restore(self._handle, cp)
        # Drop this checkpoint and everything after it.
        for extra in self._checkpoints[checkpoint_id:]:
            self._lib.ss_destroy(extra)
        del self._checkpoints[checkpoint_id:]
