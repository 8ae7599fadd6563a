"""Build + load the native state store (g++ -> shared lib, cached).

The library is built from the committed ``statestore.cpp`` into
``native/_build/`` inside the checkout (git-ignored), keyed by the
source digest — a fixed place, so a fresh checkout builds it once and
every later process of that checkout loads the same file."""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess

from ..utils.logging import LOG

_SRC = os.path.join(os.path.dirname(__file__), "statestore.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")
_LIB_CACHE: dict = {}


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"statestore-{digest}.so")


def load_statestore_lib():
    """Compile (if needed) and dlopen the state store.  Returns None —
    and says so once in the log — only when the machine has no ``g++``;
    a compiler that is present and fails is an error."""
    if "lib" in _LIB_CACHE:
        return _LIB_CACHE["lib"]
    path = _lib_path()
    if not os.path.exists(path):
        if shutil.which("g++") is None:
            LOG.warning("native state store unavailable: no g++ on PATH; "
                        "sessions use the numpy node mirrors")
            _LIB_CACHE["lib"] = None
            return None
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # Build under a private name, then rename: two processes of one
        # checkout may start together and must never dlopen a half-
        # written file.
        tmp = f"{path}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC,
                 "-o", tmp],
                check=True, capture_output=True, timeout=120)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    lib = ctypes.CDLL(path)
    d = ctypes.POINTER(ctypes.c_double)
    lib.ss_create.restype = ctypes.c_void_p
    lib.ss_create.argtypes = [ctypes.c_int64, ctypes.c_int64]
    lib.ss_destroy.argtypes = [ctypes.c_void_p]
    lib.ss_set_node.argtypes = [ctypes.c_void_p, ctypes.c_int64, d,
                                ctypes.c_double]
    lib.ss_add_task.argtypes = [ctypes.c_void_p, ctypes.c_int64, d,
                                ctypes.c_int]
    lib.ss_remove_task.argtypes = [ctypes.c_void_p, ctypes.c_int64, d,
                                   ctypes.c_int]
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.ss_add_tasks.argtypes = [ctypes.c_void_p, ctypes.c_int64, i64p, d,
                                 i32p]
    lib.ss_remove_tasks.argtypes = [ctypes.c_void_p, ctypes.c_int64, i64p,
                                    d, i32p]
    for name in ("ss_idle", "ss_allocatable", "ss_used", "ss_releasing",
                 "ss_room"):
        fn = getattr(lib, name)
        fn.restype = d
        fn.argtypes = [ctypes.c_void_p]
    lib.ss_n_nodes.restype = ctypes.c_int64
    lib.ss_n_nodes.argtypes = [ctypes.c_void_p]
    lib.ss_bulk_load.argtypes = [ctypes.c_void_p, d, d, d, d]
    lib.ss_clone.restype = ctypes.c_void_p
    lib.ss_clone.argtypes = [ctypes.c_void_p]
    lib.ss_restore.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    _LIB_CACHE["lib"] = lib
    return lib
