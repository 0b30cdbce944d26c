"""ctypes binding for the native host runtime.

≡ the reference's pybind11 extension loading (`import apex_C` etc.) —
here a plain ctypes binding with automatic build-on-first-use and pure
Python fallbacks, so the package works with or without a toolchain.
The fallbacks compute the SAME answers (the shuffle included), and a
build or load failure is reported once with its reason, never silent.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import warnings
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libapex_tpu_host.so")
_LIB = None
_TRIED = False


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    try:
        if not os.path.exists(_SO):
            subprocess.run(["sh", os.path.join(_DIR, "build_host_runtime.sh")],
                           check=True, capture_output=True, timeout=120)
        lib = ctypes.CDLL(_SO)
    except (OSError, subprocess.SubprocessError) as e:
        detail = (getattr(e, "stderr", None) or b"").decode(
            errors="replace").strip()[-400:]
        warnings.warn(
            f"apex_tpu.csrc: native host runtime unavailable ({e!r}"
            f"{': ' + detail if detail else ''}); using the pure-Python "
            "paths", RuntimeWarning, stacklevel=3)
        return None
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.flat_layout.restype = ctypes.c_int64
    lib.flat_layout.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64, i64p]
    lib.chunk_plan.restype = ctypes.c_int64
    lib.chunk_plan.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64, i64p,
                               ctypes.c_int64]
    lib.shuffle_indices.restype = None
    lib.shuffle_indices.argtypes = [ctypes.c_int64, ctypes.c_uint64, i64p]
    lib.gather_rows_f32.restype = None
    lib.gather_rows_f32.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64, i64p,
        ctypes.c_int64, ctypes.POINTER(ctypes.c_float), ctypes.c_int64]
    lib.gather_rows_i32.restype = None
    lib.gather_rows_i32.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, i64p,
        ctypes.c_int64, ctypes.POINTER(ctypes.c_int32), ctypes.c_int64]
    _LIB = lib
    return _LIB


def available() -> bool:
    return _load() is not None


def flat_layout(sizes, align: int = 1):
    """(offsets, padded_total) — aligned flat-buffer layout.
    ≡ apex_C.flatten's layout math."""
    sizes = np.ascontiguousarray(sizes, np.int64)
    lib = _load()
    if lib is None:  # pure fallback
        offsets = []
        off = 0
        for s in sizes:
            offsets.append(off)
            ps = -(-int(s) // align) * align if align > 1 else int(s)
            off += ps
        return np.asarray(offsets, np.int64), off
    out = np.empty(len(sizes), np.int64)
    total = lib.flat_layout(
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(sizes),
        align, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    return out, int(total)


def chunk_plan(sizes, chunk_size: int):
    """(tensor_idx, offset, len) work items ≡ multi_tensor_apply chunk
    metadata (csrc/multi_tensor_apply.cuh:19-60)."""
    sizes = np.ascontiguousarray(sizes, np.int64)
    max_items = int(sum(-(-int(s) // chunk_size) for s in sizes)) + 1
    lib = _load()
    if lib is None:
        items = []
        for i, s in enumerate(sizes):
            off = 0
            s = int(s)
            while s > 0:
                l = min(chunk_size, s)
                items.append((i, off, l))
                off += l
                s -= l
        return np.asarray(items, np.int64).reshape(-1, 3)
    out = np.empty((max_items, 3), np.int64)
    n = lib.chunk_plan(
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(sizes),
        chunk_size, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        max_items)
    assert n >= 0
    return out[:n]


def shuffle_indices(n: int, seed: int):
    """Deterministic Fisher-Yates permutation of [0, n)."""
    lib = _load()
    if lib is None:
        return _shuffle_indices_py(n, seed)
    out = np.empty(n, np.int64)
    lib.shuffle_indices(n, seed,
                        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    return out


def _shuffle_indices_py(n: int, seed: int):
    """host_runtime.cpp's shuffle_indices, statement for statement
    (xorshift128+ Fisher-Yates on uint64), so an epoch's order does not
    depend on whether a compiler was present."""
    mask = (1 << 64) - 1
    s0 = (seed ^ 0x9E3779B97F4A7C15) & mask
    s1 = ((seed << 1) | 0x243F6A8885A308D3) & mask

    def nxt():
        nonlocal s0, s1
        x, y = s0, s1
        s0 = y
        x ^= (x << 23) & mask
        s1 = x ^ y ^ (x >> 17) ^ (y >> 26)
        return (s1 + y) & mask

    for _ in range(8):
        nxt()
    out = list(range(n))
    for i in range(n - 1, 0, -1):
        j = nxt() % (i + 1)
        out[i], out[j] = out[j], out[i]
    return np.asarray(out, np.int64)


def gather_rows(dataset: np.ndarray, indices, num_threads: int = 4):
    """batch[b] = dataset[indices[b]] — threaded host gather (the data
    loader hot path)."""
    indices = np.ascontiguousarray(indices, np.int64)
    dataset = np.ascontiguousarray(dataset)
    lib = _load()
    if lib is None or dataset.dtype not in (np.float32, np.int32):
        return dataset[indices]
    out = np.empty((len(indices),) + dataset.shape[1:], dataset.dtype)
    row_len = int(np.prod(dataset.shape[1:]))
    if dataset.dtype == np.float32:
        lib.gather_rows_f32(
            dataset.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), row_len,
            indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(indices),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), num_threads)
    else:
        lib.gather_rows_i32(
            dataset.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), row_len,
            indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(indices),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), num_threads)
    return out
