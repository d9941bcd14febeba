"""C++ native codec bridge (ctypes).

The production CPU path, replacing the reference's SIMD assembly dependency
(klauspost/reedsolomon, reference go.mod:47). The shared library
ops/native/libseaweed_ec.so is a build product, never committed: the
first load compiles it from ops/native/seaweed_ec.cc (g++
auto-vectorization, ~2 s) when it is missing or older than its source.
A failed build is logged with the compiler's stderr and leaves the
native backend unavailable.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

from ..util.locks import make_lock
from .codec import ReedSolomonCodec

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libseaweed_ec.so")
_SRC_PATH = os.path.join(_NATIVE_DIR, "seaweed_ec.cc")
_lib = None
_load_failed = False
_load_lock = make_lock("rs_native._load_lock")


def _compile():
    """One-shot g++ build (same flags as ops/native/build.sh), written
    to a temp name and renamed so a concurrent process never dlopens a
    half-written file."""
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-march=native", "-fPIC", "-shared", "-pthread",
             "-o", tmp, _SRC_PATH],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB_PATH)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _load():
    global _lib, _load_failed
    with _load_lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            if not os.path.exists(_LIB_PATH) or \
                    os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC_PATH):
                # build before the first dlopen: replacing the file
                # after loading would keep the old mapping for the
                # process lifetime
                _compile()
            lib = ctypes.CDLL(_LIB_PATH)
        except (OSError, subprocess.SubprocessError) as e:
            from ..util import glog
            stderr = getattr(e, "stderr", b"") or b""
            glog.warningf(
                "native EC library unavailable (%s: %s); compiler "
                "stderr:\n%s", type(e).__name__, e,
                stderr.decode("utf-8", "replace").strip() or "(empty)")
            _load_failed = True
            return None
        lib.sw_ec_matmul.argtypes = [
            ctypes.POINTER(ctypes.c_uint8),  # coeffs (r*k)
            ctypes.c_int,                    # r
            ctypes.c_int,                    # k
            ctypes.POINTER(ctypes.c_uint8),  # data (k*n)
            ctypes.c_longlong,               # n
            ctypes.POINTER(ctypes.c_uint8),  # out (r*n)
        ]
        lib.sw_ec_matmul.restype = None
        lib.sw_ec_matmul_mt.argtypes = (
            lib.sw_ec_matmul.argtypes + [ctypes.c_int])  # nthreads
        lib.sw_ec_matmul_mt.restype = None
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


class NativeCodec(ReedSolomonCodec):
    """threads: 0 = hardware concurrency (matches the reference dependency's
    multi-goroutine default), 1 = single-threaded, n = exactly n."""

    backend = "native"

    def __init__(self, data_shards: int, parity_shards: int,
                 matrix_kind: str = "vandermonde", threads: int = 0):
        super().__init__(data_shards, parity_shards, matrix_kind)
        self._lib = _load()
        if self._lib is None:
            raise RuntimeError(
                f"native EC library could not be built at {_LIB_PATH} "
                f"(see the warning logged at first load)")
        self.threads = threads

    def _matmul(self, coeffs: np.ndarray, data: np.ndarray) -> np.ndarray:
        coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
        data = np.ascontiguousarray(data, dtype=np.uint8)
        r, k = coeffs.shape
        n = data.shape[1]
        out = np.zeros((r, n), dtype=np.uint8)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        if self.threads != 1:
            self._lib.sw_ec_matmul_mt(
                coeffs.ctypes.data_as(u8p), r, k,
                data.ctypes.data_as(u8p), n,
                out.ctypes.data_as(u8p), self.threads)
        else:
            self._lib.sw_ec_matmul(
                coeffs.ctypes.data_as(u8p), r, k,
                data.ctypes.data_as(u8p), n,
                out.ctypes.data_as(u8p))
        return out
