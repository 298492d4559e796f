"""ctypes bindings for libnnstpu.so (csrc/).

Build with ``make native`` at the repo root; ``load_native_lib`` also
triggers a build on demand when a toolchain is present so a fresh checkout
works without a manual step. Everything here degrades gracefully: callers
check :func:`native_available` and fall back to the pure-Python paths.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

from ..utils.log import logger

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))
_LIB_PATH = os.path.join(_REPO_ROOT, "build", "native", "libnnstpu.so")

_lock = threading.Lock()
_lib: "ctypes.CDLL | None" = None
_tried = False

RANK_LIMIT = 16
TENSOR_LIMIT = 16


class NnsTensorInfo(ctypes.Structure):
    _fields_ = [("rank", ctypes.c_uint32),
                ("dims", ctypes.c_uint32 * RANK_LIMIT),
                ("type", ctypes.c_int32)]


class NnsTensorsInfo(ctypes.Structure):
    _fields_ = [("num", ctypes.c_uint32),
                ("info", NnsTensorInfo * TENSOR_LIMIT)]


def _try_build() -> bool:
    makefile = os.path.join(_REPO_ROOT, "Makefile")
    if not os.path.exists(makefile):
        return False
    try:
        subprocess.run(["make", "-C", _REPO_ROOT, "native"], check=True,
                       capture_output=True, timeout=120)
        return True
    except (subprocess.SubprocessError, FileNotFoundError) as e:
        # said once per process (load_native_lib tries once): callers
        # such as `queue backend=auto` now run their python path
        logger.warning("native build unavailable, python paths in "
                       "use: %s", e)
        return False


def load_native_lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        # always run make: its dependency tracking makes this a no-op when
        # the .so is fresh, and rebuilds after any csrc/ change so a stale
        # binary is never silently loaded over newer source
        if not _try_build() and not os.path.exists(_LIB_PATH):
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            logger.warning("cannot load %s: %s", _LIB_PATH, e)
            return None
        lib.nns_parse_dimension.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint32)]
        lib.nns_parse_dimension.restype = ctypes.c_int
        lib.nns_serialize_dimension.argtypes = [
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_uint32,
            ctypes.c_char_p, ctypes.c_size_t]
        lib.nns_serialize_dimension.restype = ctypes.c_int
        lib.nns_element_size.argtypes = [ctypes.c_int32]
        lib.nns_element_size.restype = ctypes.c_size_t
        lib.nns_infos_are_equal.argtypes = [
            ctypes.POINTER(NnsTensorsInfo), ctypes.POINTER(NnsTensorsInfo)]
        lib.nns_infos_are_equal.restype = ctypes.c_int
        lib.nns_ring_new.argtypes = [ctypes.c_uint32]
        lib.nns_ring_new.restype = ctypes.c_void_p
        lib.nns_ring_free.argtypes = [ctypes.c_void_p]
        lib.nns_ring_close.argtypes = [ctypes.c_void_p]
        lib.nns_ring_push.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                      ctypes.c_int64]
        lib.nns_ring_push.restype = ctypes.c_int
        lib.nns_ring_pop.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_void_p),
                                     ctypes.c_int64]
        lib.nns_ring_pop.restype = ctypes.c_int
        lib.nns_ring_size.argtypes = [ctypes.c_void_p]
        lib.nns_ring_size.restype = ctypes.c_uint32
        _lib = lib
        return _lib


def native_available() -> bool:
    return load_native_lib() is not None


class NativeRing:
    """Bounded queue backed by the C++ ring; holds python objects alive
    while their ids transit the native queue."""

    def __init__(self, capacity: int):
        lib = load_native_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._ring = lib.nns_ring_new(capacity)
        self._refs = {}
        self._refs_lock = threading.Lock()
        self._next_id = [1]

    def push(self, item, timeout_ms: int = -1) -> bool:
        with self._refs_lock:
            key = self._next_id[0]
            self._next_id[0] += 1
            self._refs[key] = item
        rc = self._lib.nns_ring_push(self._ring, ctypes.c_void_p(key),
                                     timeout_ms)
        if rc != 0:
            with self._refs_lock:
                self._refs.pop(key, None)
        return rc == 0

    def pop(self, timeout_ms: int = -1):
        out = ctypes.c_void_p()
        rc = self._lib.nns_ring_pop(self._ring, ctypes.byref(out), timeout_ms)
        if rc != 0:
            return None
        with self._refs_lock:
            return self._refs.pop(out.value)

    def close(self) -> None:
        self._lib.nns_ring_close(self._ring)

    def __len__(self) -> int:
        return self._lib.nns_ring_size(self._ring)

    def __del__(self):
        try:
            if self._ring:
                self._lib.nns_ring_free(self._ring)
                self._ring = None
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass
