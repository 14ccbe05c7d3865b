"""Native host-runtime layer: lazy g++ build + ctypes bindings.

Reference analog: the reference's native pieces load the same way —
Sigar's .so is loaded if present and the JVM falls back to pure-Java
metrics when it isn't (monitor/sigar/SigarService.java:30-38). Here:
first import compiles src/estnative.cpp with g++ (cached by source
hash); every caller checks `available()` and falls back to the pure-
Python implementation when the toolchain or the build is missing.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

logger = logging.getLogger("elasticsearch_tpu.native")

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "src", "estnative.cpp")
_LOCK = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


# portable flags only: the checkout (and this cache inside it) is copied
# between hosts, so the binary must run on any x86-64 — no -march=native.
# The flags are part of the cached name, so a library built with other
# flags is never picked up.
_CXXFLAGS = ("-O3", "-shared", "-fPIC", "-std=c++17")


def _build_path() -> str:
    h = hashlib.sha256(" ".join(_CXXFLAGS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    cache = os.environ.get("EST_NATIVE_CACHE",
                           os.path.join(_HERE, "_build"))
    os.makedirs(cache, exist_ok=True)
    return os.path.join(cache, f"libestnative-{h.hexdigest()[:16]}.so")


def _compile(so_path: str) -> bool:
    # per-process scratch name: test workers that find no library all
    # build at once, and must not write through one another's file
    tmp = f"{so_path}.{os.getpid()}.tmp"
    cmd = ["g++", *_CXXFLAGS, _SRC, "-o", tmp]
    try:
        r = subprocess.run(cmd, capture_output=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.info("native build unavailable: %s", e)
        return False
    if r.returncode != 0:
        logger.warning("native build failed: %s",
                       r.stderr.decode(errors="replace")[:500])
        return False
    os.replace(tmp, so_path)
    return True


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.est_crc32.argtypes = [c.c_char_p, c.c_int64]
    lib.est_crc32.restype = c.c_uint32
    lib.est_stopset_new.argtypes = [c.c_char_p, c.c_int64]
    lib.est_stopset_new.restype = c.c_void_p
    lib.est_stopset_free.argtypes = [c.c_void_p]
    lib.est_tokenize_batch.argtypes = [
        c.c_char_p, c.POINTER(c.c_int64), c.c_int64, c.c_int, c.c_void_p,
        c.c_char_p, c.c_int64, c.POINTER(c.c_int32)]
    lib.est_tokenize_batch.restype = c.c_int64
    lib.est_wal_open.argtypes = [c.c_char_p]
    lib.est_wal_open.restype = c.c_void_p
    lib.est_wal_write.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.est_wal_write.restype = c.c_int64
    lib.est_wal_sync.argtypes = [c.c_void_p]
    lib.est_wal_sync.restype = c.c_int
    lib.est_wal_size.argtypes = [c.c_void_p]
    lib.est_wal_size.restype = c.c_int64
    lib.est_wal_close.argtypes = [c.c_void_p]
    lib.est_wal_close.restype = None
    return lib


def get_lib() -> ctypes.CDLL | None:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _LOCK:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("EST_DISABLE_NATIVE"):
            return None
        try:
            so = _build_path()
            if not os.path.exists(so) and not _compile(so):
                return None
            _lib = _bind(ctypes.CDLL(so))
            logger.debug("native layer loaded from %s", so)
        except Exception:
            logger.exception("native layer failed to load; using Python "
                             "fallbacks")
            _lib = None
    return _lib


def available() -> bool:
    return get_lib() is not None
