"""Pallas decide-kernel autotuner: pick the lane tile once, off-path.

The fused decide kernel (ops/pallas_decide.py) has exactly one tunable:
`block_b`, the per-grid-step lane tile. The right value is a device
property (VMEM budget vs DMA concurrency), so it is tuned PER DEVICE
KIND, once, during engine warmup — never on the serving path — and the
choice is cached two ways:

- in-process (`pallas_decide.register_block`), which pins the static
  jit configuration so the program warmed by `_warm_buckets` is
  byte-identical to the one serving waves dispatch (the cold-compile
  invariant, pinned by tests);
- persisted JSON beside the persistent compile cache
  (`<compile-cache-dir>/pallas_tune.json`, or GUBER_PALLAS_TUNE_CACHE),
  so an engine restart re-registers the choice WITHOUT re-running
  trials — and, because the static config is identical, the XLA/Mosaic
  executable itself comes back from the persistent compile cache
  instead of recompiling.

Trials ride the PR 11 compile telemetry (runtime/telemetry.py): each
candidate's runs are attributed via `set_shape_hint("pallas-tune:...")`
so `/debug/device`'s retrace ring shows tuning compiles as warmup-scope
(never serving-scope), and `compile_counters()` deltas are recorded per
candidate alongside wall time in the persisted stats.

Resolution order at `ensure_tuned` (env override handled downstream by
`pallas_decide.choose_block`, which always wins):

1. already registered in-process -> reuse (zero cost);
2. persisted entry for this (device kind, backend, layout, paged) key
   -> register, count a tune-cache hit;
3. tuning disabled (GUBER_PALLAS_TUNE=0) or no candidates fit -> the
   safe DEFAULT_BLOCK, NOT persisted — an unknown device falls back
   without poisoning the cache;
4. timed trials over the candidate tiles -> best wall time wins, gets
   registered + persisted.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp

from gubernator_tpu.ops import pallas_decide
from gubernator_tpu.ops.layout import RequestBatch
from gubernator_tpu.runtime import telemetry
from gubernator_tpu.utils import compilecache

log = logging.getLogger("gubernator.kerneltune")

# Candidate lane tiles, clamped per call to the serving batch width.
CANDIDATES = (128, 256, 512)

# Groups in the throwaway trial table — big enough that the DMA pattern
# is realistic, small enough that trials cost milliseconds of HBM.
_TRIAL_GROUPS = 4096
_TRIAL_RUNS = 3

# Per-key provenance for /debug + metrics: key -> dict(block=, source=,
# trials=). Sources: "persisted" | "tuned" | "default".
_stats: dict = {}
_tune_cache_hits = 0


def _env_flag(name: str, default: bool) -> bool:
    v = os.environ.get(name, "").strip().lower()
    if not v:
        return default
    return v not in ("0", "false", "no", "off")


def tune_cache_path() -> str:
    """Persisted tune-choice file: beside the persistent compile cache
    so the two survive (and are wiped) together."""
    override = os.environ.get("GUBER_PALLAS_TUNE_CACHE", "").strip()
    if override:
        return override
    return os.path.join(compilecache.cache_dir(), "pallas_tune.json")


def device_key(layout: str, paged: bool) -> str:
    """Tune-cache key: the choice is a property of the device kind and
    the program family, not of this process."""
    try:
        kind = jax.devices()[0].device_kind
    except Exception:  # pragma: no cover - no backend at all
        kind = "unknown"
    return "|".join(
        [kind, jax.default_backend(), layout, "paged" if paged else "flat"]
    )


def _load_persisted() -> dict:
    try:
        with open(tune_cache_path(), encoding="utf-8") as f:
            data = json.load(f)
        return dict(data.get("choices", {}))
    except (OSError, ValueError):
        return {}


def _persist(key: str, entry: dict) -> None:
    path = tune_cache_path()
    choices = _load_persisted()
    choices[key] = entry
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"choices": choices}, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
    except OSError as e:  # best-effort: tuning still holds in-process
        log.warning("pallas tune cache not persisted (%s): %s", path, e)


def tuning_stats() -> dict:
    """Provenance snapshot for /debug surfaces + metrics bridge."""
    return {"choices": dict(_stats), "tune_cache_hits": _tune_cache_hits}


def _trial(layout: str, batch_size: int, block: int) -> dict:
    """Time one candidate tile on a throwaway table. Runs under a tune
    shape hint so every compile it triggers is attributed to the tuner
    in the retrace ring (warmup scope, never serving)."""
    if layout == "narrow":
        from gubernator_tpu.ops.narrow import NarrowTable as T
    else:
        from gubernator_tpu.ops.fused import FusedTable as T
    ways = 8
    table = T.create(_TRIAL_GROUPS, ways)
    batch = jax.tree.map(jnp.asarray, RequestBatch.zeros(batch_size))
    now = jnp.int64(0)
    mode = pallas_decide.pallas_mode()
    telemetry.set_shape_hint(f"pallas-tune:{layout}:b{block}")
    c0 = telemetry.compile_counters()
    data = table.data
    # compile + settle
    data, out, _ = pallas_decide._flat_jit(
        data, batch, now, layout=layout, ways=ways, block_b=block, mode=mode
    )
    jax.block_until_ready(data)  # guberlint: allow-host-sync -- tune-trial compile barrier, warmup scope only
    c1 = telemetry.compile_counters()
    t0 = time.perf_counter()
    for _ in range(_TRIAL_RUNS):
        data, out, _ = pallas_decide._flat_jit(
            data, batch, now,
            layout=layout, ways=ways, block_b=block, mode=mode,
        )
    jax.block_until_ready(data)  # guberlint: allow-host-sync -- tune-trial timing barrier, warmup scope only
    wall = (time.perf_counter() - t0) / _TRIAL_RUNS
    telemetry.set_shape_hint("")
    return {
        "block": block,
        "wall_s": wall,
        "compiles": c1["compiles"] - c0["compiles"],
        "compile_seconds": round(
            c1["compile_seconds"] - c0["compile_seconds"], 4
        ),
    }


def ensure_tuned(
    layout: str, batch_size: int, *, paged: bool = False
) -> int:
    """Resolve and register the lane tile for (layout, paged) on this
    device. Called from engine warmup BEFORE the decide program warms;
    idempotent and cheap on every path but the first-ever tune."""
    global _tune_cache_hits
    if layout not in pallas_decide.PALLAS_LAYOUTS:
        return pallas_decide.DEFAULT_BLOCK
    got = pallas_decide.registered_block(layout, paged)
    if got is not None:
        return got
    key = device_key(layout, paged)

    persisted = _load_persisted().get(key)
    if isinstance(persisted, dict) and "block" in persisted:
        block = int(persisted["block"])  # guberlint: allow-host-sync -- JSON dict from disk, host-only
        pallas_decide.register_block(layout, paged, block)
        _tune_cache_hits += 1
        _stats[key] = {"block": block, "source": "persisted"}
        log.info("pallas tune: %s -> block %d (persisted)", key, block)
        return block

    candidates = sorted(
        {
            min(c, pallas_decide._pow2_at_least(max(batch_size, 1)))
            for c in CANDIDATES
        }
    )
    if not _env_flag("GUBER_PALLAS_TUNE", True) or len(candidates) < 2:
        # Unknown device / tuning off: the safe default, NOT persisted.
        block = min(
            pallas_decide.DEFAULT_BLOCK,
            pallas_decide._pow2_at_least(max(batch_size, 1)),
        )
        pallas_decide.register_block(layout, paged, block)
        _stats[key] = {"block": block, "source": "default"}
        return block

    trials = [_trial(layout, batch_size, c) for c in candidates]
    best = min(trials, key=lambda t: t["wall_s"])
    block = best["block"]
    pallas_decide.register_block(layout, paged, block)
    entry = {"block": block, "source": "tuned", "trials": trials}
    _stats[key] = entry
    _persist(key, entry)
    log.info(
        "pallas tune: %s -> block %d (%.1f us/wave, %d candidates)",
        key, block, best["wall_s"] * 1e6, len(trials),
    )
    return block
