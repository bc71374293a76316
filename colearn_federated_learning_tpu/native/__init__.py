"""ctypes loader + numpy-compatible wrappers for the native library.

``gather_rows(src, indices)`` is the public entry: a thread-parallel
``src[indices]`` for 2-D row-major arrays, used by data/sharding.py to pack
client shards.  Everything degrades to numpy when the library can't be
built (no toolchain) or is disabled via ``COLEARN_NO_NATIVE=1``.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional

import numpy as np

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def load() -> Optional[ctypes.CDLL]:
    """The native library, building it on first use; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("COLEARN_NO_NATIVE"):
            return None
        try:
            import shutil

            from colearn_federated_learning_tpu.native import build as build_mod

            lib_path = build_mod.lib_path()
            if not lib_path.exists():
                build_mod.build()
            lib = ctypes.CDLL(str(lib_path))
            lib.cl_abi_version.restype = ctypes.c_int
            if lib.cl_abi_version() != build_mod.ABI_VERSION:
                # The versioned filename makes this near-impossible (a new
                # ABI gets a new name), but if a same-name binary still
                # mismatches, rebuild and dlopen a process-unique COPY —
                # re-opening the original path would hand back the stale
                # handle this process already holds.
                build_mod.build()
                fresh = lib_path.with_name(
                    f"{lib_path.stem}.pid{os.getpid()}.so"
                )
                shutil.copy2(lib_path, fresh)
                lib = ctypes.CDLL(str(fresh))
                # The dlopen handle keeps the inode alive; unlink so the
                # per-process copies never accumulate in _build.
                try:
                    fresh.unlink()
                except OSError:
                    pass
                lib.cl_abi_version.restype = ctypes.c_int
                if lib.cl_abi_version() != build_mod.ABI_VERSION:
                    _lib = None
                    return _lib
            lib.cl_gather_rows.restype = ctypes.c_int
            lib.cl_gather_rows.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int32,
            ]
            lib.cl_topk_abs.restype = ctypes.c_int
            lib.cl_topk_abs.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
            ]
            for fold_fn in (lib.cl_fold_sparse_i8, lib.cl_fold_sparse_f32):
                fold_fn.restype = ctypes.c_int
                fold_fn.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64,
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                    ctypes.c_float, ctypes.c_float, ctypes.c_int32,
                ]
            _lib = lib
        except Exception:
            _lib = None
        return _lib


def topk_abs(flat: np.ndarray, k: int,
             n_threads: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """Indices (ascending) and values of the ``k`` largest-|x| entries of a
    1-D float32 array — thread-parallel nth_element when the native
    library is present, numpy argpartition otherwise.  The top-k update
    sparsifier's host-side hot op (fed/compression.py)."""
    flat = np.ascontiguousarray(flat, dtype=np.float32)
    k = int(k)
    if not 0 < k <= flat.size:
        raise ValueError(f"k={k} out of range for size {flat.size}")
    lib = load()
    if lib is not None and flat.size > 0:
        idx = np.empty(k, np.int32)
        val = np.empty(k, np.float32)
        if n_threads <= 0:
            n_threads = min(16, os.cpu_count() or 1)
        rc = lib.cl_topk_abs(flat.ctypes.data, flat.size, k,
                             idx.ctypes.data, val.ctypes.data, n_threads)
        if rc == 0:
            return idx, val
    idx = np.argpartition(np.abs(flat), flat.size - k)[-k:]
    idx = np.sort(idx).astype(np.int32)
    return idx, flat[idx]


def fold_sparse(acc: np.ndarray, idx: np.ndarray, vals: np.ndarray,
                scale: float, w: float, set_mode: bool) -> bool:
    """Fused ``acc.reshape(-1)[idx] (=|+=) (vals * scale) * w`` — the
    ops/fold_kernel.py native lowering (dequant + weight + scatter in one
    pass, fold.cpp).  ``acc`` must be a writable C-contiguous flat float32
    array, ``idx`` int64, ``vals`` int8 (topk8 raw) or float32 (topk).
    Returns False when the native library is unavailable — the caller
    falls back to the equivalent numpy expression."""
    lib = load()
    if lib is None:
        return False
    if not (isinstance(acc, np.ndarray) and acc.dtype == np.float32
            and acc.flags.c_contiguous and acc.flags.writeable):
        raise ValueError("fold_sparse needs a writable C-contiguous "
                         "float32 accumulator")
    idx = np.ascontiguousarray(idx, np.int64)
    if vals.dtype == np.int8:
        fn = lib.cl_fold_sparse_i8
        vals = np.ascontiguousarray(vals)
    else:
        fn = lib.cl_fold_sparse_f32
        vals = np.ascontiguousarray(vals, np.float32)
    rc = fn(acc.ctypes.data, acc.size, idx.ctypes.data, vals.ctypes.data,
            idx.size, float(scale), float(w), 1 if set_mode else 0)
    if rc != 0:
        raise IndexError("fold_sparse: index out of range")
    return True


def gather_rows(src: np.ndarray, indices: np.ndarray,
                n_threads: int = 0) -> np.ndarray:
    """``src[indices]`` over the leading axis, thread-parallel when the
    native library is present; plain numpy take otherwise.  ``src`` may be
    any-dimensional; rows are its trailing dims."""
    lib = load()
    if lib is None:
        return np.take(src, indices, axis=0)
    src = np.ascontiguousarray(src)
    idx = np.ascontiguousarray(indices, dtype=np.int64)
    out = np.empty((idx.shape[0],) + src.shape[1:], dtype=src.dtype)
    row_bytes = int(np.prod(src.shape[1:], dtype=np.int64)) * src.itemsize
    if row_bytes == 0 or idx.size == 0:
        return out
    if n_threads <= 0:
        n_threads = min(16, os.cpu_count() or 1)
    rc = lib.cl_gather_rows(
        src.ctypes.data, src.shape[0], row_bytes,
        idx.ctypes.data, idx.shape[0],
        out.ctypes.data, n_threads,
    )
    if rc != 0:
        raise IndexError("gather_rows: index out of range")
    return out
