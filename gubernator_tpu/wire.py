"""Columnar wire path: C++ protobuf parse/build for the serving edge.

The Python protobuf round trip costs ~10µs per request item; at the
north-star request rates that is the entire budget. This module loads
the library built from native/wirepath.cc (on demand, like the batch
hasher — utils/nativebuild.py) and exposes:

- parse_requests(data) -> RequestColumns | None: one pass over a
  GetRateLimitsReq's bytes into numpy columns + concatenated
  `name + "_" + unique_key` key bytes. None means the native library is
  unavailable or the payload is malformed (caller falls back to the
  protobuf object path; malformed bytes then fail with the proper gRPC
  decode error).
- build_responses(status, limit, remaining, reset_time) -> bytes: a
  GetRateLimitsResp built straight from response columns.
- fnv1_batch(key_data, offsets, variant) -> uint64 hashes for vectorized
  ring routing (same fnv1/fnv1a as parallel/hash_ring.py).
"""

from __future__ import annotations

import ctypes
import dataclasses
import os
from typing import Optional

import numpy as np

from gubernator_tpu.utils import lockorder, nativebuild

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native"
)
_SRC = os.path.join(_NATIVE_DIR, "wirepath.cc")

_lock = lockorder.make_lock("wire.load")
_lib: Optional[ctypes.CDLL] = None
_tried = False
# Why load() returned None, for the daemon's start-up WARNING.
unavailable_reason = ""


def load() -> Optional[ctypes.CDLL]:
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
            lib.guber_count_requests.argtypes = [
                ctypes.c_char_p, ctypes.c_int,
                ctypes.POINTER(ctypes.c_int64),
            ]
            lib.guber_count_requests.restype = ctypes.c_int
            lib.guber_parse_requests.argtypes = [
                ctypes.c_char_p, ctypes.c_int,
                np.ctypeslib.ndpointer(np.int64),   # hits
                np.ctypeslib.ndpointer(np.int64),   # limit
                np.ctypeslib.ndpointer(np.int64),   # duration
                np.ctypeslib.ndpointer(np.int32),   # algo
                np.ctypeslib.ndpointer(np.int64),   # behavior
                np.ctypeslib.ndpointer(np.int64),   # burst
                np.ctypeslib.ndpointer(np.int64),   # created_at
                np.ctypeslib.ndpointer(np.uint8),   # has_created
                np.ctypeslib.ndpointer(np.uint8),   # slow
                np.ctypeslib.ndpointer(np.int64),   # name_lens
                np.ctypeslib.ndpointer(np.uint8),   # key_data
                np.ctypeslib.ndpointer(np.int64),   # key_offsets
            ]
            lib.guber_parse_requests.restype = ctypes.c_int
            lib.guber_build_responses.argtypes = [
                ctypes.c_int,
                np.ctypeslib.ndpointer(np.int8),
                np.ctypeslib.ndpointer(np.int64),
                np.ctypeslib.ndpointer(np.int64),
                np.ctypeslib.ndpointer(np.int64),
                np.ctypeslib.ndpointer(np.uint8),
            ]
            lib.guber_build_responses.restype = ctypes.c_int64
            lib.guber_responses_size.argtypes = [ctypes.c_int]
            lib.guber_responses_size.restype = ctypes.c_int64
            lib.guber_build_responses_md.argtypes = [
                ctypes.c_int,
                np.ctypeslib.ndpointer(np.int8),
                np.ctypeslib.ndpointer(np.int64),
                np.ctypeslib.ndpointer(np.int64),
                np.ctypeslib.ndpointer(np.int64),
                np.ctypeslib.ndpointer(np.uint8),   # owner_data
                np.ctypeslib.ndpointer(np.int64),   # owner_offsets
                np.ctypeslib.ndpointer(np.uint8),
            ]
            lib.guber_build_responses_md.restype = ctypes.c_int64
            lib.guber_responses_size_md.argtypes = [
                ctypes.c_int, ctypes.c_int64,
            ]
            lib.guber_responses_size_md.restype = ctypes.c_int64
            for name in ("guber_fnv1_batch", "guber_fnv1a_batch"):
                fn = getattr(lib, name)
                fn.argtypes = [
                    np.ctypeslib.ndpointer(np.uint8),
                    np.ctypeslib.ndpointer(np.int64),
                    ctypes.c_int,
                    np.ctypeslib.ndpointer(np.uint64),
                ]
            _lib = lib
        except OSError as e:
            unavailable_reason = f"{so}: {e}"
            _lib = None
        return _lib


def available() -> bool:
    return load() is not None


@dataclasses.dataclass
class RequestColumns:
    """Columnar view of a GetRateLimitsReq."""

    n: int
    hits: np.ndarray  # int64
    limit: np.ndarray  # int64
    duration: np.ndarray  # int64
    algo: np.ndarray  # int32
    behavior: np.ndarray  # int64
    burst: np.ndarray  # int64
    created_at: np.ndarray  # int64
    has_created: np.ndarray  # uint8
    slow: np.ndarray  # uint8 (metadata present)
    name_lens: np.ndarray  # int64 (for vectorized validation)
    key_data: np.ndarray  # uint8, concatenated hash keys
    key_offsets: np.ndarray  # int64, n+1

    def key_string(self, i: int) -> str:
        lo, hi = int(self.key_offsets[i]), int(self.key_offsets[i + 1])
        return bytes(self.key_data[lo:hi]).decode("utf-8", errors="replace")

    def key_strings_all(self) -> list:
        """All key strings in one pass (one bytes materialization + plain
        bytes slicing — ~3x cheaper than per-item key_string calls)."""
        raw = self.key_data.tobytes()
        offs = self.key_offsets.tolist()
        return [
            raw[offs[i] : offs[i + 1]].decode("utf-8", errors="replace")
            for i in range(self.n)
        ]

    def name_key_parts(self, i: int) -> tuple:
        """(name, unique_key) for item i, split at the BYTE level.

        name_lens counts BYTES (wirepath.cc); slicing the decoded string
        by it would mis-split multi-byte UTF-8 names — so split the raw
        bytes first, then decode each part."""
        lo, hi = int(self.key_offsets[i]), int(self.key_offsets[i + 1])
        raw = bytes(self.key_data[lo:hi])
        nl = int(self.name_lens[i])
        return (
            raw[:nl].decode("utf-8", errors="replace"),
            raw[nl + 1 :].decode("utf-8", errors="replace"),
        )


# RequestColumns' fields that hold one value an item (the rest: n, and
# the key bytes with their offsets).
PER_ITEM_FIELDS = (
    "hits", "limit", "duration", "algo", "behavior", "burst", "created_at",
    "has_created", "slow", "name_lens",
)


def concat_columns(parts) -> "RequestColumns":
    """Several calls' columns as one, in the order given: the per-item
    fields concatenated, `key_data` appended and each part's
    `key_offsets` shifted by the key bytes before it (the engine's group
    commit, runtime/engine.py check_columns). The parts are not
    changed."""
    cat = np.concatenate
    bases = np.cumsum([len(p.key_data) for p in parts])
    offsets = [parts[0].key_offsets]
    offsets += [
        p.key_offsets[1:] + b for p, b in zip(parts[1:], bases.tolist())
    ]
    fields = {
        f: cat([getattr(p, f) for p in parts]) for f in PER_ITEM_FIELDS
    }
    return RequestColumns(
        n=sum(p.n for p in parts),
        key_data=cat([p.key_data for p in parts]),
        key_offsets=cat(offsets),
        **fields,
    )


def req_from_columns(cols: "RequestColumns", i: int):
    """RateLimitReq object for one lane — the single shared builder for
    every consumer that needs objects from wire columns (forwarding path,
    store read-through). Field semantics must match the protobuf object
    path exactly."""
    from gubernator_tpu.api.types import RateLimitReq

    name, unique_key = cols.name_key_parts(i)
    created = int(cols.created_at[i])
    return RateLimitReq(
        name=name,
        unique_key=unique_key,
        algorithm=int(cols.algo[i]),
        behavior=int(cols.behavior[i]),
        hits=int(cols.hits[i]),
        limit=int(cols.limit[i]),
        duration=int(cols.duration[i]),
        burst=int(cols.burst[i]),
        created_at=created if cols.has_created[i] and created != 0 else None,
    )


def parse_requests(data: bytes) -> Optional[RequestColumns]:
    lib = load()
    if lib is None:
        return None
    kb = ctypes.c_int64()
    n = lib.guber_count_requests(data, len(data), ctypes.byref(kb))
    if n < 0:
        return None
    if n == 0:
        z64 = np.empty(0, dtype=np.int64)
        return RequestColumns(
            0, z64, z64, z64, np.empty(0, np.int32), z64, z64, z64,
            np.empty(0, np.uint8), np.empty(0, np.uint8), z64,
            np.empty(0, np.uint8), np.zeros(1, np.int64),
        )
    hits = np.empty(n, np.int64)
    limit = np.empty(n, np.int64)
    duration = np.empty(n, np.int64)
    algo = np.empty(n, np.int32)
    behavior = np.empty(n, np.int64)
    burst = np.empty(n, np.int64)
    created = np.empty(n, np.int64)
    has_created = np.empty(n, np.uint8)
    slow = np.empty(n, np.uint8)
    name_lens = np.empty(n, np.int64)
    key_data = np.empty(max(int(kb.value), 1), np.uint8)
    key_offsets = np.empty(n + 1, np.int64)
    got = lib.guber_parse_requests(
        data, len(data), hits, limit, duration, algo, behavior, burst,
        created, has_created, slow, name_lens, key_data, key_offsets,
    )
    if got != n:
        return None
    return RequestColumns(
        n, hits, limit, duration, algo, behavior, burst, created,
        has_created, slow, name_lens, key_data, key_offsets,
    )


def build_responses(status, limit, remaining, reset_time) -> bytes:
    lib = load()
    assert lib is not None
    n = len(status)
    out = np.empty(int(lib.guber_responses_size(n)), np.uint8)
    written = lib.guber_build_responses(
        n,
        np.ascontiguousarray(status, dtype=np.int8),
        np.ascontiguousarray(limit, dtype=np.int64),
        np.ascontiguousarray(remaining, dtype=np.int64),
        np.ascontiguousarray(reset_time, dtype=np.int64),
        out,
    )
    return out[:written].tobytes()


def build_responses_md(
    status, limit, remaining, reset_time, owner_data, owner_offsets
) -> bytes:
    """build_responses + per-item metadata={"owner": ...} for items with
    a nonzero owner span (the GLOBAL non-owner answer contract)."""
    lib = load()
    assert lib is not None
    n = len(status)
    odata = np.ascontiguousarray(owner_data, dtype=np.uint8)
    ooffs = np.ascontiguousarray(owner_offsets, dtype=np.int64)
    out = np.empty(
        int(lib.guber_responses_size_md(n, int(ooffs[-1]))), np.uint8
    )
    written = lib.guber_build_responses_md(
        n,
        np.ascontiguousarray(status, dtype=np.int8),
        np.ascontiguousarray(limit, dtype=np.int64),
        np.ascontiguousarray(remaining, dtype=np.int64),
        np.ascontiguousarray(reset_time, dtype=np.int64),
        odata,
        ooffs,
        out,
    )
    return out[:written].tobytes()


def fnv1_batch(key_data: np.ndarray, key_offsets: np.ndarray, variant: str = "fnv1") -> np.ndarray:
    lib = load()
    assert lib is not None
    n = len(key_offsets) - 1
    out = np.empty(n, np.uint64)
    fn = lib.guber_fnv1_batch if variant == "fnv1" else lib.guber_fnv1a_batch
    fn(key_data, key_offsets, n, out)
    if variant == "fnv1a-mix":
        # murmur3 fmix64 finalizer, vectorized (must match
        # hash_ring.fmix64 bit-for-bit — ring placement parity).
        with np.errstate(over="ignore"):
            out ^= out >> np.uint64(33)
            out *= np.uint64(0xFF51AFD7ED558CCD)
            out ^= out >> np.uint64(33)
            out *= np.uint64(0xC4CEB9FE1A85EC53)
            out ^= out >> np.uint64(33)
    return out
