"""Native (C++) runtime components: build-on-first-use via g++, bound with
ctypes (this image has no pybind11 — SURVEY §7 note on the C++ seam).

Components:
- tcp_store.cc  — rendezvous KV store (tcp_store.h:121 parity)
- shm_ring.cc   — shared-memory batch transport for DataLoader workers
                  (mmap_allocator.cc parity)

The compiled library is cached next to the sources keyed by a source hash;
callers must tolerate ``lib() is None`` (no toolchain) and fall back to the
pure-Python paths."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_SRC_DIR = os.path.join(os.path.dirname(__file__), "src")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")
_SOURCES = ["tcp_store.cc", "shm_ring.cc"]

_lock = threading.Lock()
_lib = None
_tried = False


def _source_hash() -> str:
    h = hashlib.sha256()
    for s in _SOURCES:
        with open(os.path.join(_SRC_DIR, s), "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def _build() -> str | None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    out = os.path.join(_BUILD_DIR, f"libpaddle_tpu_native_{_source_hash()}.so")
    if os.path.exists(out):
        return out
    srcs = [os.path.join(_SRC_DIR, s) for s in _SOURCES]
    # a temporary name of this process's own: test workers that import
    # this module at once each build and rename their own file (one
    # shared name let the first rename take another's output away)
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread",
           *srcs, "-lrt", "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (subprocess.CalledProcessError, FileNotFoundError,
            subprocess.TimeoutExpired):
        return None
    os.replace(tmp, out)
    return out


def lib() -> ctypes.CDLL | None:
    """The loaded native library, building it on first use; None if the
    toolchain is unavailable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        path = _build()
        if path is None:
            return None
        L = ctypes.CDLL(path)
        # tcp_store
        L.tcpstore_server_start.restype = ctypes.c_void_p
        L.tcpstore_server_start.argtypes = [ctypes.c_int]
        L.tcpstore_server_port.restype = ctypes.c_int
        L.tcpstore_server_port.argtypes = [ctypes.c_void_p]
        L.tcpstore_server_stop.argtypes = [ctypes.c_void_p]
        L.tcpstore_connect.restype = ctypes.c_int
        L.tcpstore_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                       ctypes.c_int]
        L.tcpstore_close.argtypes = [ctypes.c_int]
        L.tcpstore_set.restype = ctypes.c_int
        L.tcpstore_set.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                   ctypes.c_char_p, ctypes.c_uint32]
        L.tcpstore_get.restype = ctypes.c_int64
        L.tcpstore_get.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                   ctypes.c_char_p, ctypes.c_uint32]
        L.tcpstore_add.restype = ctypes.c_int64
        L.tcpstore_add.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                   ctypes.c_int64]
        L.tcpstore_wait.restype = ctypes.c_int
        L.tcpstore_wait.argtypes = [ctypes.c_int, ctypes.c_char_p]
        L.tcpstore_check.restype = ctypes.c_int
        L.tcpstore_check.argtypes = [ctypes.c_int, ctypes.c_char_p]
        L.tcpstore_delete.restype = ctypes.c_int
        L.tcpstore_delete.argtypes = [ctypes.c_int, ctypes.c_char_p]
        # shm_ring
        L.shm_ring_open.restype = ctypes.c_void_p
        L.shm_ring_open.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                    ctypes.c_int]
        L.shm_ring_close.argtypes = [ctypes.c_void_p]
        L.shm_ring_mark_closed.argtypes = [ctypes.c_void_p]
        L.shm_ring_push.restype = ctypes.c_int
        L.shm_ring_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_uint64]
        L.shm_ring_peek.restype = ctypes.c_int64
        L.shm_ring_peek.argtypes = [ctypes.c_void_p]
        L.shm_ring_try_peek.restype = ctypes.c_int64
        L.shm_ring_try_peek.argtypes = [ctypes.c_void_p]
        L.shm_ring_pop.restype = ctypes.c_int64
        L.shm_ring_pop.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_uint64]
        _lib = L
        return _lib
