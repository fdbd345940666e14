"""Native batch key hasher: build-on-demand C++ via ctypes.

Loads the library built from native/guberhash.cc (utils/nativebuild.py
builds it with g++ on first use, named by the source's hash) and
exposes single and batch 128-bit hashing. The in-process table identity
hash is swappable (it never crosses process boundaries — peers route by
fnv1 over strings and all wire/state formats carry string keys), so when
the native library is available the whole process uses MurmurHash3
x64-128 from C; otherwise everything falls back to Python xxh3. The
choice is static per process, keeping hashes self-consistent.
"""

from __future__ import annotations

import ctypes
import os
from typing import List, Optional, Tuple

import numpy as np

from gubernator_tpu.utils import lockorder, nativebuild

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
_SRC = os.path.join(_NATIVE_DIR, "guberhash.cc")

_lock = lockorder.make_lock("native.load")
_lib: Optional[ctypes.CDLL] = None
_tried = False
# Why load() returned None, for the daemon's start-up WARNING.
unavailable_reason = ""


def load() -> Optional[ctypes.CDLL]:
    """The native library, building it on first use; None if unavailable."""
    global _lib, _tried, unavailable_reason
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so, unavailable_reason = nativebuild.build_library(_SRC)
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
            lib.guber_hash128.argtypes = [
                ctypes.c_char_p,
                ctypes.c_int,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.guber_hash128_batch.argtypes = [
                ctypes.c_char_p,
                np.ctypeslib.ndpointer(np.int64),
                ctypes.c_int,
                ctypes.c_uint64,
                np.ctypeslib.ndpointer(np.uint64),
                np.ctypeslib.ndpointer(np.uint64),
                np.ctypeslib.ndpointer(np.int32),
            ]
            _lib = lib
        except OSError as e:
            unavailable_reason = f"{so}: {e}"
            _lib = None
        return _lib


def available() -> bool:
    return load() is not None


def hash128(key: str) -> Tuple[int, int]:
    """Single-key native hash as signed int64 halves."""
    lib = load()
    assert lib is not None
    raw = key.encode("utf-8")
    hi = ctypes.c_uint64()
    lo = ctypes.c_uint64()
    lib.guber_hash128(raw, len(raw), ctypes.byref(hi), ctypes.byref(lo))
    to_signed = lambda v: v - (1 << 64) if v >= (1 << 63) else v  # noqa: E731
    return to_signed(hi.value), to_signed(lo.value)


def hash128_batch_raw(
    data: bytes, offsets: np.ndarray, num_groups: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Batch hash over pre-concatenated key bytes (the columnar edge path
    hands these straight from the wire parser — no string objects)."""
    lib = load()
    assert lib is not None
    n = len(offsets) - 1
    hi = np.empty(n, dtype=np.uint64)
    lo = np.empty(n, dtype=np.uint64)
    group = np.empty(n, dtype=np.int32)
    lib.guber_hash128_batch(data, offsets, n, num_groups, hi, lo, group)
    return hi.view(np.int64), lo.view(np.int64), group


def hash128_batch(
    keys: List[str], num_groups: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Batch hash: returns (hi, lo) as int64 arrays and group as int32."""
    lib = load()
    assert lib is not None
    encoded = [k.encode("utf-8") for k in keys]
    offsets = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum([len(e) for e in encoded], out=offsets[1:])
    data = b"".join(encoded)
    n = len(keys)
    hi = np.empty(n, dtype=np.uint64)
    lo = np.empty(n, dtype=np.uint64)
    group = np.empty(n, dtype=np.int32)
    lib.guber_hash128_batch(data, offsets, n, num_groups, hi, lo, group)
    return hi.view(np.int64), lo.view(np.int64), group
