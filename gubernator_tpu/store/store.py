"""Persistence seams: Loader (checkpoint/restore) and Store (durability).

The reference defines two plugin interfaces (reference store.go:49-78):
- Loader: bulk Load() at startup, Save() at shutdown — exactly
  checkpoint/resume (SURVEY.md §5).
- Store: OnChange after every update (write-behind) + Get on cache miss
  (read-through) + Remove.

TPU adaptation (SURVEY.md §7): hooks fire at *batch* granularity. After
each decide batch the engine gathers the touched rows from the device
(ops.decide.gather_rows — exact raw state, fixed-point leaky fraction
included) and hands them to Store.on_change; read-through consults the
store for keys this process has never seen before dispatching them.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Iterable, List, Optional, Protocol

from gubernator_tpu.utils import lockorder
from gubernator_tpu.api.types import Algorithm, RateLimitReq


@dataclasses.dataclass
class ItemSnapshot:
    """One key's raw counter state — the portable form of a slot row
    (the reference's CacheItem + bucket struct, store.go:29-43)."""

    key: str  # hash_key (name + "_" + unique_key)
    algorithm: int = Algorithm.TOKEN_BUCKET
    status: int = 0
    limit: int = 0
    duration: int = 0
    remaining: int = 0  # raw: whole tokens (token) / Q44.20 (leaky)
    stamp: int = 0  # created_at (token) / updated_at (leaky)
    expire_at: int = 0
    invalid_at: int = 0
    burst: int = 0


class Store(Protocol):
    """Write-behind + read-through durability plugin
    (reference store.go:49-65, batch-granular here)."""

    def on_change(self, items: List[ItemSnapshot]) -> None: ...

    def get(self, req: RateLimitReq) -> Optional[ItemSnapshot]: ...

    def remove(self, key: str) -> None: ...


class Loader(Protocol):
    """Bulk checkpoint/restore plugin (reference store.go:69-78)."""

    def load(self) -> Iterable[ItemSnapshot]: ...

    def save(self, items: Iterable[ItemSnapshot]) -> None: ...


class MemoryStore:
    """Dict-backed Store (the reference's exported MockStore analog,
    store.go:80-112) — usable in tests and as a template."""

    def __init__(self):
        self.data: Dict[str, ItemSnapshot] = {}
        self.lock = lockorder.make_lock("store.memory")
        self.get_calls = 0
        self.change_calls = 0

    def on_change(self, items: List[ItemSnapshot]) -> None:
        # Ownership of the snapshot objects transfers to the store (the
        # engine builds them fresh per flush and never mutates them
        # afterwards), so no defensive copy.
        with self.lock:
            self.change_calls += 1
            for it in items:
                self.data[it.key] = it

    def get(self, req: RateLimitReq) -> Optional[ItemSnapshot]:
        with self.lock:
            self.get_calls += 1
            it = self.data.get(req.hash_key())
            return dataclasses.replace(it) if it is not None else None

    def remove(self, key: str) -> None:
        with self.lock:
            self.data.pop(key, None)


class MemoryLoader:
    """List-backed Loader (reference MockLoader analog, store.go:114-150)."""

    def __init__(self, items: Optional[List[ItemSnapshot]] = None):
        self.items: List[ItemSnapshot] = list(items or [])
        self.called_load = 0
        self.called_save = 0

    def load(self) -> Iterable[ItemSnapshot]:
        self.called_load += 1
        return list(self.items)

    def save(self, items: Iterable[ItemSnapshot]) -> None:
        self.called_save += 1
        self.items = list(items)


# ---- engine glue -----------------------------------------------------------


def snapshots_from_engine(engine) -> List[ItemSnapshot]:
    """Drain the engine's table into portable snapshots (Loader.Save feed;
    reference workers.go:451-534)."""
    import numpy as np

    snap = engine.snapshot()
    keys = snap["key_strings"]
    used = np.asarray(snap["used"])
    out: List[ItemSnapshot] = []
    idx = np.nonzero(used)[0]
    for i in idx:
        hi, lo = int(snap["key_hi"][i]), int(snap["key_lo"][i])
        key = keys.get((hi, lo))
        if key is None:
            continue  # anonymous row (key dictionary disabled)
        out.append(
            ItemSnapshot(
                key=key,
                algorithm=int(snap["algo"][i]),
                status=int(snap["status"][i]),
                limit=int(snap["limit"][i]),
                duration=int(snap["duration"][i]),
                remaining=int(snap["remaining"][i]),
                stamp=int(snap["stamp"][i]),
                expire_at=int(snap["expire_at"][i]),
                invalid_at=int(snap["invalid_at"][i]),
                burst=int(snap["burst"][i]),
            )
        )
    return out


def merge_snapshots_lww(engine, items: List[ItemSnapshot]) -> tuple:
    """Last-writer-wins merge of incoming snapshots into an engine table
    (the receiver half of ring-change handover, docs/robustness.md).

    Unlike inject_snapshots' unconditional overwrite (correct for the
    Loader restore into an empty table and for authoritative GLOBAL
    broadcasts), a handover can race live traffic at the receiver: the
    new owner may already have served hits for a moved key by the time
    the old owner's snapshot arrives. Resolution, per key:

    - strictly newer local `stamp` wins (the receiver re-created the
      bucket after the sender snapshotted it — its writes are newer);
    - equal stamps: the MORE-CONSUMED side wins (lower `remaining`).
      Equal stamps mean both sides hold copies of the same bucket
      (handover echo, or a drain re-ship racing post-transfer hits at
      the successor); within a window hits only consume, so the lower
      remaining carries strictly more of the true count.

    Returns (accepted, stale) counts."""
    import numpy as np

    from gubernator_tpu.api.keys import key_hash128

    if not items:
        return 0, 0
    snap = engine.snapshot()
    used = np.asarray(snap["used"])
    idx = np.nonzero(used)[0]
    hi_col, lo_col = snap["key_hi"], snap["key_lo"]
    stamp_col, rem_col = snap["stamp"], snap["remaining"]
    existing: Dict[tuple, tuple] = {}
    for i in idx:
        existing[(int(hi_col[i]), int(lo_col[i]))] = (
            int(stamp_col[i]),
            int(rem_col[i]),
        )
    # inject_snapshots overwrites verbatim in list order, so same-key
    # duplicates inside one batch must be reduced by the SAME rule here
    # — otherwise the last duplicate wins positionally and the merged
    # state depends on arrival order (non-convergent under re-delivery).
    def _loses(have: tuple, s: ItemSnapshot) -> bool:
        return have[0] > s.stamp or (have[0] == s.stamp and have[1] <= s.remaining)

    keep: Dict[tuple, ItemSnapshot] = {}
    stale = 0
    for s in items:
        kh = key_hash128(s.key)
        have = existing.get(kh)
        if have is not None and _loses(have, s):
            stale += 1
            continue
        prev = keep.get(kh)
        if prev is not None:
            if _loses((prev.stamp, prev.remaining), s):
                stale += 1
                continue
            stale += 1  # prev superseded within the batch
        keep[kh] = s
    engine.inject_snapshots(list(keep.values()))
    return len(keep), stale


def save_engine(engine, loader: Loader) -> int:
    items = snapshots_from_engine(engine)
    loader.save(items)
    return len(items)


def load_engine(engine, loader: Loader) -> int:
    """Stream loader items into the engine table before serving
    (reference gubernator.go:138-148 -> workers.go:329-446)."""
    items = list(loader.load())
    engine.inject_snapshots(items)
    return len(items)


def attach_store(engine, store: Store) -> None:
    """Enable read-through + write-behind on an engine: a DeviceEngine
    on one chip, or the mesh engines (MeshEngine, IciEngine) over an
    owner-sharded table. The contract is the same on both: every
    acknowledged change of an ordinary key's bucket is handed to the
    Store before the next flush's, by the sequence's row gather, which
    on a mesh reads each lane's row at the chip that owns it; a key the
    table evicted is read back into its owner's shard. GLOBAL buckets of
    an IciEngine's replica tier are not persisted
    (docs/architecture.md "A Store on the sharded table").
    gubernator_store_rows_skipped counts a lane whose row the gather
    did not find, and stays 0.

    Read-through correctness is driven by the device-table residency
    probe and write-behind keys come from each request, so the host
    key-string dictionary is not required. Keeping keep_key_strings=True
    (the default) is still recommended: it lets the engine prefetch
    never-seen keys OUTSIDE the device lock and keeps Loader snapshots
    carrying original key strings."""
    engine.store = store
    # Warm the store-path kernels now: the first flush otherwise
    # cold-compiles probe_exists/gather_rows while holding the serving
    # lock (~1s on CPU, tens of seconds on TPU), stalling forwarded
    # batches past their timeout and inviting client-retry double-apply —
    # the same rationale as the engine's _warmup for decide/inject.
    warm = getattr(engine, "warm_store_path", None)
    if warm is not None:
        warm()
