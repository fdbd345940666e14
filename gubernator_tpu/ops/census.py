"""Table census: layout-generic residency/age/churn scan of the slot table.

The paged-table roadmap (ROADMAP item 1: host-DRAM cold tier) needs
evidence about WHICH slots are cold, how much HBM is wasted on
expired-but-resident entries, and how group fill pressure is
distributed — none of which `occupancy_stats()`'s two scalars can say.
This module is that observation layer: ONE jitted, non-donating
program per table layout that scans the resident table and returns
O(buckets) device scalars (never O(slots) host transfer):

- log2 histograms of slot AGE (now - stamp: time since the counter
  window was created/updated) and IDLE time (now - lru: time since the
  slot last served a request), over used slots;
- a fixed-width per-group-region occupancy heatmap — the future "page"
  axis: region r aggregates a contiguous run of groups, exactly the
  granularity a demotion policy would page at;
- expired-but-still-resident waste (used slots whose remaining window
  has fully elapsed: expire_at <= now);
- probe pressure: the per-group used-way fill histogram plus the
  longest run of completely full groups (full groups force unexpired
  evictions on insert);
- a cold-set summary: used-slot counts whose idle time exceeds
  k x the slot's own duration, for a static tuple of multipliers
  (1x/4x/16x by default) — `count * bytes_per_slot` is the HBM a cold
  tier would reclaim at that aggressiveness.

Conventions shared with the numpy oracle (bit-exactness is pinned by
tests/test_table_census.py):

- ages/idles clamp negative deltas (wraparound or future stamps from
  injected state) to 0 — they land in bucket 0, never underflow;
- histogram bin 0 counts deltas < 1 ms; bin i counts [2^(i-1), 2^i) ms;
  the last bin absorbs everything >= 2^(n_buckets-2) ms (np.searchsorted
  semantics on the shared power-of-two boundary vector);
- the heatmap pads the group axis up to heatmap_width * ceil(G/R)
  with empty groups, so trailing regions may aggregate fewer groups.

The program is built from the layout's traceable `to_wide` (the same
converter the ici sync tick uses), so one implementation covers
wide and fused and both ici tiers; the replica tier passes
`stacked=True` and the program scans replica 0's table (replicas
mirror each other post-sync).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.ops.kernels import get_raw_kernels

I64 = jnp.int64

# Shared defaults (EngineConfig / IciEngineConfig mirror these; the
# metrics exposition derives its `le` bounds from N_BUCKETS, so the
# catalog stays in lockstep without importing jax).
CENSUS_BUCKETS = 32  # log2 ms bins: bin 31 is >= ~12.4 days
DEFAULT_HEATMAP_WIDTH = 64
DEFAULT_THRESHOLDS = (1, 4, 16)  # cold = idle > k x slot duration


class CensusOutput(NamedTuple):
    """O(buckets) device arrays from one census scan."""

    live: jnp.ndarray  # () int64 used slots
    full_groups: jnp.ndarray  # () int64 groups with all ways used
    waste: jnp.ndarray  # () int64 used & expire_at <= now
    age_hist: jnp.ndarray  # (n_buckets,) int64 log2 ms bins of now-stamp
    age_sum: jnp.ndarray  # () int64 total clamped age ms over used slots
    idle_hist: jnp.ndarray  # (n_buckets,) int64 log2 ms bins of now-lru
    idle_sum: jnp.ndarray  # () int64 total clamped idle ms over used slots
    heatmap: jnp.ndarray  # (heatmap_width,) int64 used slots per region
    fill_hist: jnp.ndarray  # (ways+1,) int64 groups by used-way count
    max_full_run: jnp.ndarray  # () int64 longest run of full groups
    cold: jnp.ndarray  # (len(thresholds),) int64 used & idle > k*duration
    # Per-region count of cold slots (idle > thresholds[0] x duration),
    # same region axis/padding as `heatmap` — the demotion policy's
    # victim signal (runtime/pager.py demote_victims): a region full of
    # USED slots may still be all-cold, and the pager's LRU touch ticks
    # cannot see that (one probe re-warms a whole page).
    cold_heatmap: jnp.ndarray  # (heatmap_width,) int64 cold slots per region


def _log2_bins(values: jnp.ndarray, used: jnp.ndarray, n_buckets: int):
    """(counts, sum) of `values` over used lanes in log2-ms bins."""
    v = jnp.where(used, jnp.maximum(values, jnp.int64(0)), jnp.int64(0))
    bounds = jnp.int64(2) ** jnp.arange(n_buckets - 1, dtype=I64)
    idx = jnp.searchsorted(bounds, v, side="right")
    ones = jnp.where(used, jnp.int64(1), jnp.int64(0))
    counts = jnp.zeros((n_buckets,), dtype=I64).at[idx].add(ones)
    return counts, jnp.sum(v, dtype=I64)


def _census_wide(
    wide, now, *, ways: int, heatmap_width: int, thresholds, n_buckets: int
) -> CensusOutput:
    used = wide.used
    n = used.shape[0]
    groups = n // ways
    age = now - wide.stamp
    idle = now - wide.lru

    age_hist, age_sum = _log2_bins(age, used, n_buckets)
    idle_hist, idle_sum = _log2_bins(idle, used, n_buckets)

    live = jnp.sum(used, dtype=I64)
    waste = jnp.sum(used & (wide.expire_at <= now), dtype=I64)

    g_used = jnp.sum(
        used.reshape(groups, ways), axis=1, dtype=I64
    )
    full = g_used == ways
    full_groups = jnp.sum(full, dtype=I64)
    fill_hist = (
        jnp.zeros((ways + 1,), dtype=I64)
        .at[g_used]
        .add(jnp.ones((groups,), dtype=I64))
    )
    # Longest run of consecutive full groups: distance to the most
    # recent non-full group (running max of its index), 0 outside runs.
    # associative_scan, not lax.cummax: on TPU the cum* primitives lower
    # to a reduce-window whose compile time explodes with length
    # (262,144 groups: 205 s on a v5e against 12 s for this scan).
    g_idx = jnp.arange(groups, dtype=I64)
    last_unfull = jax.lax.associative_scan(
        jnp.maximum, jnp.where(~full, g_idx, jnp.int64(-1))
    )
    max_full_run = jnp.max(
        jnp.where(full, g_idx - last_unfull, jnp.int64(0))
    )

    per_region = -(-groups // heatmap_width)  # ceil
    padded = (
        jnp.zeros((heatmap_width * per_region,), dtype=I64)
        .at[:groups]
        .set(g_used)
    )
    heatmap = jnp.sum(
        padded.reshape(heatmap_width, per_region), axis=1, dtype=I64
    )

    idle_c = jnp.maximum(idle, jnp.int64(0))
    cold = jnp.stack(
        [
            jnp.sum(
                used & (idle_c > jnp.int64(k) * wide.duration), dtype=I64
            )
            for k in thresholds
        ]
    )

    cold0 = used & (idle_c > jnp.int64(thresholds[0]) * wide.duration)
    g_cold = jnp.sum(cold0.reshape(groups, ways), axis=1, dtype=I64)
    cold_padded = (
        jnp.zeros((heatmap_width * per_region,), dtype=I64)
        .at[:groups]
        .set(g_cold)
    )
    cold_heatmap = jnp.sum(
        cold_padded.reshape(heatmap_width, per_region), axis=1, dtype=I64
    )

    return CensusOutput(
        live=live,
        full_groups=full_groups,
        waste=waste,
        age_hist=age_hist,
        age_sum=age_sum,
        idle_hist=idle_hist,
        idle_sum=idle_sum,
        heatmap=heatmap,
        fill_hist=fill_hist,
        max_full_run=max_full_run,
        cold=cold,
        cold_heatmap=cold_heatmap,
    )


@functools.lru_cache(maxsize=None)
def make_census(
    layout: str,
    ways: int,
    heatmap_width: int = DEFAULT_HEATMAP_WIDTH,
    thresholds: tuple = DEFAULT_THRESHOLDS,
    n_buckets: int = CENSUS_BUCKETS,
    stacked: bool = False,
):
    """One jitted census program: (table, now) -> CensusOutput.

    NON-donating by construction (plain jax.jit, no donate_argnums):
    the engine dispatches it on the live table reference between
    flushes, and the table must survive. `stacked=True` builds the
    replica-tier variant whose input leaves carry a leading device
    axis; it scans replica 0 (post-sync replicas are mirrors)."""
    RK = get_raw_kernels(layout)

    def impl(table, now):
        if stacked:
            table = jax.tree.map(lambda x: x[0], table)
        # Scope names are profile metadata (docs/monitoring.md
        # "/debug/profile"); the outputs are unchanged.
        with jax.named_scope("census.layout_in"):
            wide = RK.to_wide(table)
        with jax.named_scope("census.scan"):
            return _census_wide(
                wide,
                now,
                ways=ways,
                heatmap_width=heatmap_width,
                thresholds=tuple(thresholds),
                n_buckets=n_buckets,
            )

    return jax.jit(impl)


# ---------------------------------------------------------------------------
# Pure-numpy oracle (tests/test_table_census.py pins bit-exactness)


def census_oracle(
    wide,
    now: int,
    *,
    ways: int,
    heatmap_width: int = DEFAULT_HEATMAP_WIDTH,
    thresholds: tuple = DEFAULT_THRESHOLDS,
    n_buckets: int = CENSUS_BUCKETS,
) -> dict:
    """Reference census over a WIDE table of host numpy arrays; mirrors
    _census_wide decision-for-decision (same clamps, same searchsorted
    boundaries, same heatmap padding)."""
    def h(col, dt):
        return np.asarray(col, dtype=dt)  # guberlint: allow-host-sync -- pure-numpy oracle over host reference arrays (test differential target, never serving)

    used = h(wide.used, bool)
    stamp = h(wide.stamp, np.int64)
    lru = h(wide.lru, np.int64)
    expire_at = h(wide.expire_at, np.int64)
    duration = h(wide.duration, np.int64)
    n = used.shape[0]
    groups = n // ways
    bounds = np.int64(2) ** np.arange(n_buckets - 1, dtype=np.int64)

    def bins(deltas):
        v = np.where(used, np.maximum(deltas, 0), 0).astype(np.int64)
        idx = np.searchsorted(bounds, v, side="right")
        counts = np.bincount(
            idx[used], minlength=n_buckets
        ).astype(np.int64)
        return counts, np.int64(v.sum())

    age_hist, age_sum = bins(np.int64(now) - stamp)
    idle = np.int64(now) - lru
    idle_hist, idle_sum = bins(idle)

    g_used = used.reshape(groups, ways).sum(axis=1).astype(np.int64)
    full = g_used == ways
    g_idx = np.arange(groups, dtype=np.int64)
    last_unfull = np.maximum.accumulate(np.where(~full, g_idx, -1))
    max_full_run = int(np.where(full, g_idx - last_unfull, 0).max())

    per_region = -(-groups // heatmap_width)
    padded = np.zeros(heatmap_width * per_region, dtype=np.int64)
    padded[:groups] = g_used
    heatmap = padded.reshape(heatmap_width, per_region).sum(axis=1)

    idle_c = np.maximum(idle, 0)
    cold = np.array(
        [
            int((used & (idle_c > np.int64(k) * duration)).sum())
            for k in thresholds
        ],
        dtype=np.int64,
    )

    cold0 = used & (idle_c > np.int64(thresholds[0]) * duration)
    g_cold = cold0.reshape(groups, ways).sum(axis=1).astype(np.int64)
    cold_padded = np.zeros(heatmap_width * per_region, dtype=np.int64)
    cold_padded[:groups] = g_cold
    cold_heatmap = cold_padded.reshape(heatmap_width, per_region).sum(axis=1)

    return {
        "live": int(used.sum()),
        "full_groups": int(full.sum()),
        "waste": int((used & (expire_at <= np.int64(now))).sum()),
        "age_hist": age_hist,
        "age_sum": int(age_sum),
        "idle_hist": idle_hist,
        "idle_sum": int(idle_sum),
        "heatmap": heatmap.astype(np.int64),
        "fill_hist": np.bincount(
            g_used, minlength=ways + 1
        ).astype(np.int64),
        "max_full_run": max_full_run,
        "cold": cold,
        "cold_heatmap": cold_heatmap.astype(np.int64),
    }
