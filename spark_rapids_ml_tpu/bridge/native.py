"""ctypes loader for the native columnar library (libsrml_tpu.so).

The reference packages its native library inside the jar and extracts it at
first use (JniRAPIDSML.java:34-58). Here the .so is built from
``native/src/columnar.cpp`` (``make -C native``) and looked up next to the
package and in the repo's ``native/build`` dir; if absent or disabled via
config ``use_native_bridge``, callers take the pure-NumPy path (the
portable one). Which path was chosen is logged once per process.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional

import numpy as np

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.utils.logging import get_logger

logger = get_logger("bridge.native")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_tried = False

_SO_NAME = "libsrml_tpu.so"


def _candidate_paths():
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(here))
    return [
        # Explicit config wins over discovery.
        os.environ.get("SRML_TPU_NATIVE_LIB", ""),
        os.path.join(here, _SO_NAME),
        os.path.join(repo, "native", "build", _SO_NAME),
    ]


def get_lib() -> Optional[ctypes.CDLL]:
    """Load and memoize the native library; None if unavailable/disabled."""
    global _lib, _lib_tried
    if not config.get("use_native_bridge"):
        return None
    with _lock:
        if _lib_tried:
            return _lib
        _lib_tried = True
        for path in _candidate_paths():
            if path and os.path.exists(path):
                try:
                    lib = ctypes.CDLL(path)
                    _configure(lib)
                except (OSError, AttributeError) as e:
                    # AttributeError: stale .so missing a newer export —
                    # try the next candidate, then the NumPy path.
                    logger.warning(
                        "native columnar library %s failed to load: %s",
                        path, e,
                    )
                    continue
                _lib = lib
                logger.info("columnar bridge: native library %s", path)
                break
        if _lib is None:
            logger.info(
                "columnar bridge: NumPy path (no loadable %s; build it "
                "with `make -C native`)", _SO_NAME,
            )
        return _lib


def _configure(lib: ctypes.CDLL) -> None:
    c_i64 = ctypes.c_int64
    c_p = ctypes.c_void_p
    # int srml_flatten_list_f64(const double* values, const int64_t* offsets,
    #                           int64_t n_rows, int64_t n_cols, double* out,
    #                           int n_threads)
    lib.srml_flatten_list_f64.restype = ctypes.c_int
    lib.srml_flatten_list_f64.argtypes = [c_p, c_p, c_i64, c_i64, c_p, ctypes.c_int]
    lib.srml_flatten_list_f32.restype = ctypes.c_int
    lib.srml_flatten_list_f32.argtypes = [c_p, c_p, c_i64, c_i64, c_p, ctypes.c_int]
    # int srml_cast_f64_to_f32(const double* src, int64_t n, float* dst, int n_threads)
    lib.srml_cast_f64_to_f32.restype = ctypes.c_int
    lib.srml_cast_f64_to_f32.argtypes = [c_p, c_i64, c_p, ctypes.c_int]
    # int srml_concat_chunks_f64(const double** chunks, const int64_t* rows,
    #                            int64_t n_chunks, int64_t n_cols, double* out,
    #                            int n_threads)
    lib.srml_concat_chunks_f64.restype = ctypes.c_int
    lib.srml_concat_chunks_f64.argtypes = [c_p, c_p, c_i64, c_i64, c_p, ctypes.c_int]
    lib.srml_abi_version.restype = ctypes.c_int
    lib.srml_abi_version.argtypes = []
    if lib.srml_abi_version() != 1:
        raise OSError("libsrml_tpu ABI version mismatch")


def _nthreads() -> int:
    return min(16, os.cpu_count() or 1)


def flatten_ragged(values: np.ndarray, offsets: np.ndarray, n_cols: int) -> Optional[np.ndarray]:
    """Native gather of a ragged list column into an (n_rows, n_cols) matrix.

    ``values`` is the flat child buffer, ``offsets`` the (n_rows+1,) int64
    offsets. Every row must have exactly ``n_cols`` elements (validated
    natively; returns None to signal fallback on any error).
    """
    lib = get_lib()
    if lib is None:
        return None
    n_rows = len(offsets) - 1
    if n_rows < 0:
        return None
    # Bounds check here on the host: the native side never sees the values
    # length, and a corrupt offsets buffer must not become an OOB memcpy.
    if n_rows > 0 and (int(offsets[0]) < 0 or int(offsets[-1]) > values.size):
        return None
    if values.dtype == np.float64:
        fn = lib.srml_flatten_list_f64
        out = np.empty((n_rows, n_cols), dtype=np.float64)
    elif values.dtype == np.float32:
        fn = lib.srml_flatten_list_f32
        out = np.empty((n_rows, n_cols), dtype=np.float32)
    else:
        return None
    values = np.ascontiguousarray(values)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    rc = fn(
        values.ctypes.data_as(ctypes.c_void_p),
        offsets.ctypes.data_as(ctypes.c_void_p),
        n_rows,
        n_cols,
        out.ctypes.data_as(ctypes.c_void_p),
        _nthreads(),
    )
    if rc != 0:
        return None
    return out


def cast_f64_to_f32(src: np.ndarray) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None or src.dtype != np.float64:
        return None
    src = np.ascontiguousarray(src)
    dst = np.empty(src.shape, dtype=np.float32)
    rc = lib.srml_cast_f64_to_f32(
        src.ctypes.data_as(ctypes.c_void_p),
        src.size,
        dst.ctypes.data_as(ctypes.c_void_p),
        _nthreads(),
    )
    if rc != 0:
        return None
    return dst


def concat_chunks_f64(chunks) -> Optional[np.ndarray]:
    """Threaded concat of a list of contiguous (rows_i, d) float64 blocks."""
    lib = get_lib()
    if lib is None or not chunks:
        return None
    arrs = [np.ascontiguousarray(c) for c in chunks]
    if any(a.dtype != np.float64 or a.ndim != 2 for a in arrs):
        return None
    d = arrs[0].shape[1]
    if any(a.shape[1] != d for a in arrs):
        return None
    n_total = sum(a.shape[0] for a in arrs)
    out = np.empty((n_total, d), dtype=np.float64)
    ptrs = (ctypes.c_void_p * len(arrs))(
        *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrs]
    )
    rows = np.asarray([a.shape[0] for a in arrs], dtype=np.int64)
    rc = lib.srml_concat_chunks_f64(
        ctypes.cast(ptrs, ctypes.c_void_p),
        rows.ctypes.data_as(ctypes.c_void_p),
        len(arrs),
        d,
        out.ctypes.data_as(ctypes.c_void_p),
        _nthreads(),
    )
    if rc != 0:
        return None
    return out
