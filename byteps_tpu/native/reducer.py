"""ctypes loader for libbyteps_native.so with build-on-first-use.

API:
  available() -> bool
  sum_into(dst, src)           # dst += src elementwise, OpenMP-parallel
  key_to_shard(key, n) -> int  # reference global.cc:305-334 hash
  omp_max_threads() -> int
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from ..common import logging as bps_log

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "libbyteps_native.so")
_STAMP = os.path.join(_HERE, "libbyteps_native.stamp")
_CSRC = os.path.normpath(os.path.join(_HERE, "..", "..", "csrc"))
_SRCS = [
    os.path.join(_CSRC, "byteps_native.cc"),
    os.path.join(_CSRC, "data_loader.cc"),
]
_SRC = _SRCS[0]  # existence probe

_lib: Optional[ctypes.CDLL] = None
_lock = threading.Lock()
_build_failed = False


# No -march=native: the chip tool copies the working tree (this .so
# included — it is git-ignored, not copy-ignored) to a machine with a
# different CPU, where host-tuned code can fault with SIGILL.
_CXXFLAGS = ["-O3", "-fopenmp", "-pthread", "-fPIC", "-std=c++17",
             "-shared"]


def _source_digest() -> str:
    """Hash of what the binary is built FROM (source bytes + flags).
    Staleness is decided by content, never mtime: a checkout, an
    archive export and a copied tree all stamp files with arbitrary
    times."""
    h = hashlib.sha256(" ".join(_CXXFLAGS).encode())
    for src in _SRCS:
        if os.path.exists(src):
            with open(src, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def _build(digest: str) -> bool:
    """Compile the native lib in place (g++ is in the baked image) and
    stamp it with the digest of its inputs."""
    srcs = [s for s in _SRCS if os.path.exists(s)]
    # build beside the target, then rename: a concurrent process (tests
    # spawn many) must never dlopen a half-written file
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = [os.environ.get("CXX", "g++"), *_CXXFLAGS, "-o", tmp, *srcs]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        with open(tmp, "w") as f:
            f.write(digest)
        os.replace(tmp, _STAMP)
    except (OSError, subprocess.SubprocessError) as e:
        bps_log.warning("native build failed (%s); using numpy fallback", e)
        return False
    return True


def _stamp() -> str:
    try:
        with open(_STAMP) as f:
            return f.read().strip()
    except OSError:
        return ""


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        digest = _source_digest()
        if not os.path.exists(_SO) or _stamp() != digest:
            if not os.path.exists(_SRC) or not _build(digest):
                _build_failed = True
                return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:  # pragma: no cover
            bps_log.warning("native load failed: %s", e)
            _build_failed = True
            return None
        for name, argtypes in [
            ("bps_sum_f32", [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]),
            ("bps_sum_f64", [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]),
            ("bps_sum_f16", [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]),
            ("bps_sum_bf16", [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]),
            ("bps_sum_i32", [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]),
            ("bps_sum_i64", [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]),
        ]:
            getattr(lib, name).argtypes = argtypes
            getattr(lib, name).restype = None
        lib.bps_key_to_shard.argtypes = [ctypes.c_uint64, ctypes.c_int64]
        lib.bps_key_to_shard.restype = ctypes.c_int64
        lib.bps_omp_max_threads.restype = ctypes.c_int
        lib.bps_abi_version.restype = ctypes.c_int
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


_SUM_FN = {
    np.dtype(np.float32): "bps_sum_f32",
    np.dtype(np.float64): "bps_sum_f64",
    np.dtype(np.float16): "bps_sum_f16",
    np.dtype(np.int32): "bps_sum_i32",
    np.dtype(np.int64): "bps_sum_i64",
}
try:
    import ml_dtypes

    _SUM_FN[np.dtype(ml_dtypes.bfloat16)] = "bps_sum_bf16"
except ImportError:  # pragma: no cover
    pass


def sum_into(dst: np.ndarray, src: np.ndarray) -> None:
    """dst += src, OpenMP-parallel (reference CpuReducer::sum,
    cpu_reducer.cc:41-155).  Falls back to numpy if the lib is missing."""
    lib = _load()
    src = np.ascontiguousarray(src, dtype=dst.dtype)
    fn_name = _SUM_FN.get(dst.dtype)
    if lib is None or fn_name is None or not dst.flags.c_contiguous:
        dst += src
        return
    getattr(lib, fn_name)(
        dst.ctypes.data_as(ctypes.c_void_p),
        src.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(dst.size),
    )


def key_to_shard(key: int, num_shards: int) -> int:
    lib = _load()
    if lib is None:
        return (((key >> 16) + (key % 65536)) * 9973) % max(num_shards, 1)
    return int(lib.bps_key_to_shard(key, num_shards))


def omp_max_threads() -> int:
    lib = _load()
    return int(lib.bps_omp_max_threads()) if lib is not None else 1
