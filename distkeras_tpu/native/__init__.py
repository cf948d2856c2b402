"""ctypes bindings for the native input-pipeline kernels.

Builds ``native/dataloader.cc`` into a shared library on first use
(g++, cached next to this package) and exposes :func:`gather_rows` /
:func:`gather_normalize_u8`.  Everything degrades to numpy when no
compiler is available — the native path is an optimization of the data
plane, never a requirement (the reference's data plane performance
likewise came from its substrate, Spark; SURVEY.md §2 native census).

The cached library is named by a hash of its source file's bytes, so a
binary is only ever loaded if it was built from the ``.cc`` that sits
in the checkout now: a stale or foreign ``.so`` in this directory
(``*.so`` is ignored by git, and a copy of the tree carries whatever
lies on disk) has another name and is never opened.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

import numpy as np

from distkeras_tpu.utils.locks import TracedLock

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native")
_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_NATIVE_DIR, "dataloader.cc")
_BPE_SRC = os.path.join(_NATIVE_DIR, "tokenizer.cc")

# Build-cache lock (leaf): held across the one-time g++ build — a
# long first acquire by design, never on a serving/training hot path.
_lock = TracedLock("native.build")
_lib = None
_tried = False
_bpe_lib = None
_bpe_tried = False

_DEF_THREADS = min(8, os.cpu_count() or 1)


def _artifact(src: str, stem: str) -> str | None:
    """Where the library built from exactly these source bytes lives;
    None when the source is missing."""
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        return None
    return os.path.join(_PKG_DIR, f"{stem}.{digest}.so")


def _compile(src: str, so: str) -> str | None:
    """g++ one source file into a shared library; None on any failure
    (no compiler, bad toolchain) — callers fall back to numpy/python.
    Built under a private name and renamed into place, so no process
    ever opens a half-written library."""
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", src, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError):
        return None
    return so


def _load(src: str, stem: str):
    """The library for ``src`` — the cached build of these exact bytes,
    else a fresh one — or None (no source, no compiler, load error)."""
    so = _artifact(src, stem)
    if so is None or not (os.path.exists(so) or _compile(src, so)):
        return None
    try:
        return ctypes.CDLL(so)
    except OSError:
        return None


def bpe_lib():
    """The BPE tokenizer library (native/tokenizer.cc), or None."""
    global _bpe_lib, _bpe_tried
    with _lock:
        if _bpe_lib is not None or _bpe_tried:
            return _bpe_lib
        _bpe_tried = True
        handle = _load(_BPE_SRC, "_libdkt_bpe")
        if handle is None:
            return None
        handle.dkt_bpe_train.restype = ctypes.c_int32
        handle.dkt_bpe_train.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p]
        handle.dkt_bpe_encode.restype = ctypes.c_int64
        handle.dkt_bpe_encode.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_void_p]
        handle.dkt_bpe_decode.restype = ctypes.c_int64
        handle.dkt_bpe_decode.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64]
        _bpe_lib = handle
        return _bpe_lib


def lib():
    """The loaded library, building it if needed; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        handle = _load(_SRC, "_libdkt_data")
        if handle is None:
            return None
        handle.dkt_gather_f32.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int]
        handle.dkt_gather_bytes.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int]
        handle.dkt_gather_u8_normalize.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_float, ctypes.c_float,
            ctypes.c_int]
        _lib = handle
        return _lib


def available() -> bool:
    return lib() is not None


def _as_c(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a)


def _check_idx(idx: np.ndarray, n_rows: int) -> np.ndarray:
    """Bounds-check (both paths, so numpy fallback matches native: no
    negative-index wrapping) and coerce to contiguous int64."""
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    if idx.size and (idx.min() < 0 or idx.max() >= n_rows):
        raise IndexError(f"gather index out of range for {n_rows} rows")
    return idx


def _check_out(out: np.ndarray, shape: tuple, dtype) -> np.ndarray:
    if out.shape != shape or out.dtype != np.dtype(dtype):
        raise ValueError(
            f"out buffer mismatch: need {shape} {np.dtype(dtype)}, got "
            f"{out.shape} {out.dtype}")
    if not out.flags.c_contiguous:
        raise ValueError("out buffer must be C-contiguous (reshape of a "
                         "non-contiguous buffer would write into a copy)")
    return out


def gather_rows(src: np.ndarray, idx: np.ndarray,
                out: np.ndarray | None = None,
                n_threads: int = _DEF_THREADS) -> np.ndarray:
    """``src[idx]`` for row-major arrays, multithreaded when native.

    Equivalent to numpy fancy indexing on axis 0; the native path runs
    the row memcpys across threads (fancy indexing is single-threaded).
    """
    handle = lib()
    src = _as_c(src)
    idx = _check_idx(idx, len(src))
    out_shape = (len(idx), *src.shape[1:])
    if out is not None:
        out = _check_out(out, out_shape, src.dtype)
    if handle is None:
        result = src[idx]
        if out is not None:
            out[...] = result
            return out
        return result
    if out is None:
        out = np.empty(out_shape, src.dtype)
    if idx.size == 0:
        # the reshape(n, -1)s below raise for size-0 arrays (this also
        # covers an empty src, where len(src) rows can't reshape either)
        return out
    rows = src.reshape(len(src), -1)
    flat_out = out.reshape(len(idx), -1)
    if src.dtype == np.float32:
        handle.dkt_gather_f32(
            rows.ctypes.data, idx.ctypes.data, flat_out.ctypes.data,
            len(idx), rows.shape[1], n_threads)
    else:
        handle.dkt_gather_bytes(
            rows.view(np.uint8).ctypes.data, idx.ctypes.data,
            flat_out.view(np.uint8).ctypes.data,
            len(idx), rows.shape[1] * src.dtype.itemsize, n_threads)
    return out


def gather_normalize_u8(src: np.ndarray, idx: np.ndarray, scale: float,
                        bias: float = 0.0, out: np.ndarray | None = None,
                        n_threads: int = _DEF_THREADS) -> np.ndarray:
    """``src[idx].astype(f32) * scale + bias`` fused (uint8 images)."""
    if src.dtype != np.uint8:
        raise TypeError(f"gather_normalize_u8 needs uint8, got {src.dtype}")
    handle = lib()
    src = _as_c(src)
    idx = _check_idx(idx, len(src))
    out_shape = (len(idx), *src.shape[1:])
    if out is not None:
        out = _check_out(out, out_shape, np.float32)
    if handle is None:
        result = src[idx].astype(np.float32) * scale + bias
        if out is not None:
            out[...] = result
            return out
        return result
    if out is None:
        out = np.empty(out_shape, np.float32)
    if idx.size == 0:
        # reshape(0, -1) below would raise; nothing to copy anyway.
        return out
    handle.dkt_gather_u8_normalize(
        src.reshape(len(src), -1).ctypes.data, idx.ctypes.data,
        out.reshape(len(idx), -1).ctypes.data,
        len(idx), int(np.prod(src.shape[1:])), scale, bias, n_threads)
    return out
