"""HBM accounting: per-subsystem device-memory attribution + headroom.

The paged slot table (ROADMAP item 1) cannot be built or tuned blind:
its two governing numbers are "how much HBM does each resident
structure cost" and "how much headroom is left before the next
allocation OOMs". This module answers the first from engine geometry
(each engine names its resident subsystems — slot table, ICI replica
tier, census buffers, pipeline in-flight ring, snapshot staging — and
sizes them from bytes_per_slot x capacity) and the second from the
backend's real per-device allocator stats when they exist.

Two sources, ONE schema (tests/test_device_observatory.py pins parity),
summed over every device the engine spans with one row per device:

- "device": jax `device.memory_stats()` — real allocator numbers
  (TPU/GPU backends). bytes_in_use/bytes_limit come from the device;
  the subsystem map stays the geometry-derived attribution, and the
  gap is reported as unattributed_bytes.
- "estimated": the CPU-safe fallback (CPU backends return no memory
  stats; jax may be absent entirely). bytes_in_use is the sum of the
  subsystem estimates and the capacity is ESTIMATED_CAPACITY_BYTES —
  a documented single-chip assumption, not a measurement — so tier-1
  CPU runs exercise every consumer of the snapshot shape.

Deliberately jax-free at import: jax loads lazily inside
device_stats(), and a CPU-pinned process never touches it beyond one
failed stats probe.
"""

from __future__ import annotations

import logging
from typing import Optional

log = logging.getLogger("gubernator_tpu.devicemem")

SCHEMA_VERSION = 1

# Capacity assumption for the estimated fallback, used ONLY when the
# backend exposes no allocator stats: one v5e core's 16 GiB HBM. The
# snapshot labels itself source="estimated" so dashboards can tell a
# real headroom number from this assumption.
ESTIMATED_CAPACITY_BYTES = 16 << 30


def process_devices() -> dict:
    """What JAX initialised in this process, as JAX reports it. JAX
    falls back to CPU by itself when no accelerator initialises; this
    is where a daemon says which one it got."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


def device_stats(device=None) -> Optional[dict]:
    """Raw allocator stats for `device` (default: the first jax device),
    or None when unavailable — jax absent, no devices, or a backend
    (CPU) whose devices expose no memory_stats. Never raises."""
    try:
        import jax

        dev = device if device is not None else jax.devices()[0]
        stats = dev.memory_stats()
    except Exception:
        return None
    if not stats or "bytes_in_use" not in stats:
        return None
    return dict(stats)


def _device_row(device, stats: Optional[dict]) -> dict:
    """One `devices` row: identity plus the device's own allocator
    numbers (None when its backend reports none)."""
    in_use = int(stats.get("bytes_in_use", 0)) if stats else None
    return {
        "id": getattr(device, "id", None),
        "platform": getattr(device, "platform", None),
        "device_kind": getattr(device, "device_kind", None),
        "bytes_in_use": in_use,
        "peak_bytes_in_use": (
            int(stats.get("peak_bytes_in_use", in_use)) if stats else None
        ),
        "bytes_limit": (
            int(
                stats.get("bytes_limit", 0)
                or stats.get("bytes_reservable_limit", 0)
                or 0
            )
            if stats
            else None
        ),
    }


def snapshot(
    subsystems: Optional[dict] = None,
    devices=None,
    capacity_bytes: Optional[int] = None,
) -> dict:
    """One device-memory accounting snapshot over `devices` — every
    device the engine's tables span (default: the first jax device).

    `subsystems` maps subsystem name -> estimated resident bytes (static
    geometry, computed once by the engine at init). The returned dict
    has the SAME keys whether backed by real device stats or the
    estimated fallback; only `source` distinguishes them. The totals sum
    over the devices; `devices` carries one row per device (identity +
    its own allocator numbers, None where the backend reports none) so
    a table that landed on the wrong chip, or all on one, is visible."""
    subs = {k: int(v) for k, v in (subsystems or {}).items()}
    accounted = sum(subs.values())
    devs = list(devices) if devices else [None]
    rows = [_device_row(d, device_stats(d)) for d in devs]
    if all(r["bytes_in_use"] is not None for r in rows):
        source = "device"
        in_use = sum(r["bytes_in_use"] for r in rows)
        peak = sum(r["peak_bytes_in_use"] for r in rows)
        limit = sum(r["bytes_limit"] for r in rows)
    else:
        source = "estimated"
        in_use = accounted
        limit = 0
        peak = in_use
    if limit <= 0:
        limit = int(capacity_bytes or ESTIMATED_CAPACITY_BYTES * len(devs))
    headroom = max(limit - in_use, 0)
    return {
        "v": SCHEMA_VERSION,
        "source": source,
        "bytes_in_use": in_use,
        "peak_bytes_in_use": peak,
        "bytes_limit": limit,
        "headroom_bytes": headroom,
        "headroom_frac": headroom / limit if limit else 0.0,
        "subsystems": subs,
        "accounted_bytes": accounted,
        "unattributed_bytes": max(in_use - accounted, 0),
        "devices": rows,
    }
