"""Native (C++) runtime components, compiled on demand with g++.

The reference links LevelDB/LMDB/SQLite as native storage engines; here the
equivalent embedded engine is ``lockbox.cc``, built once into a shared
library and loaded via ctypes (no pybind11 in the image).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_BUILD_LOCK = threading.Lock()
_LIB = None
_HASH_LIB = None


def _build_dir() -> str:
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "build")
    os.makedirs(d, exist_ok=True)
    return d


def _compile_and_load(src_name: str, so_name: str, extra_flags=()) -> ctypes.CDLL:
    """Compile ``src_name`` into ``so_name`` (unless already built) and load
    it.  The library's file name carries a hash of the source bytes and the
    compiler command, so a library built from other source or flags (a
    ``build/`` copied along with a checkout) is never loaded."""
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), src_name)
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", None, src,
           *extra_flags]
    h = hashlib.sha256(repr(cmd[:6] + cmd[7:]).encode())
    with open(src, "rb") as f:
        h.update(f.read())
    stem, ext = os.path.splitext(so_name)
    so = os.path.join(_build_dir(), f"{stem}-{h.hexdigest()[:16]}{ext}")
    if not os.path.exists(so):
        tmp = f"{so}.{os.getpid()}.tmp"
        cmd[6] = tmp
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, so)
    return ctypes.CDLL(so)


def load_hash_pairs() -> ctypes.CDLL:
    """Compile (if needed) and load the batched SHA-256 pair hasher."""
    global _HASH_LIB
    if _HASH_LIB is not None:
        return _HASH_LIB
    with _BUILD_LOCK:
        if _HASH_LIB is not None:
            return _HASH_LIB
        lib = _compile_and_load("hash_pairs.cc", "libhashpairs.so",
                                ["-ldl", "-lpthread"])
        lib.hash_pairs.restype = ctypes.c_int
        lib.hash_pairs.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
        ]
        _HASH_LIB = lib
        return lib


def load_lockbox() -> ctypes.CDLL:
    """Compile (if needed) and load the lockbox shared library."""
    global _LIB
    if _LIB is not None:
        return _LIB
    with _BUILD_LOCK:
        if _LIB is not None:
            return _LIB
        lib = _compile_and_load("lockbox.cc", "liblockbox.so")
        lib.lockbox_open.restype = ctypes.c_void_p
        lib.lockbox_open.argtypes = [ctypes.c_char_p]
        lib.lockbox_close.argtypes = [ctypes.c_void_p]
        lib.lockbox_put.restype = ctypes.c_int
        lib.lockbox_put.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
            ctypes.c_char_p, ctypes.c_uint32,
        ]
        lib.lockbox_get.restype = ctypes.c_int64
        lib.lockbox_get.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
            ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.lockbox_delete.restype = ctypes.c_int
        lib.lockbox_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
        lib.lockbox_count.restype = ctypes.c_uint64
        lib.lockbox_count.argtypes = [ctypes.c_void_p]
        lib.lockbox_keys.restype = ctypes.c_uint64
        lib.lockbox_keys.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
            ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.lockbox_flush.restype = ctypes.c_int
        lib.lockbox_flush.argtypes = [ctypes.c_void_p]
        lib.lockbox_compact.restype = ctypes.c_int
        lib.lockbox_compact.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return lib
