"""Admission accounting: ground-truth admitted-vs-limit scan of the table.

After PR 13 a single check can be answered by five different paths with
different staleness (owner engine, GLOBAL replica, degraded-local,
lease-local debit, columnar fastpath) — yet nothing measured whether
the fleet actually ENFORCES the configured limits. This module is the
ground-truth half of the admission observatory (docs/monitoring.md
"Admission"): ONE jitted, non-donating program per table layout that
scans the resident table and reduces per-key admitted-this-window
vs. configured limit to O(buckets) device scalars (never O(slots) host
transfer):

- admitted-this-window per key: `limit - tokens_remaining`, where
  whole tokens remaining is the raw `remaining` column for token
  buckets and `remaining >> FIXED_SHIFT` (arithmetic shift, the
  reference's int64 truncation) for leaky buckets' Q44.20 level;
  clamped at 0 — a bursted slot (remaining > limit) has admitted 0,
  not a negative count;
- per-key EXCESS: `max(0, admitted - limit)` — hits the table itself
  admitted beyond the configured limit (non-zero only when `remaining`
  went negative, e.g. injected or reconciled state);
- sums of admitted/limit over active keys (the over-admission SLI
  numerator/denominator: `excess_sum / limit_sum`), excess key count,
  max per-key excess, OVER_LIMIT key count, and a log2 histogram of
  per-key excess (same searchsorted boundary conventions as
  ops/census.py, pinned bit-exact by the shared oracle tests).

"Active" means: used, limit > 0, and the window has not fully elapsed
(`expire_at > now`) — an expired-but-resident slot's counters describe
a PAST window and must not feed the current-window SLI.

The device scan is owner-LOCAL truth. The fleet-wide SLI reconciles it
with the lease ledger (carved-but-unreconciled slice hits) and GLOBAL
in-flight replica admissions in the engine/auditor layers — see
runtime/engine.py admission_snapshot and parallel/auditor.py.

The program is built from the layout's traceable `to_wide` (same as the
census), so one implementation covers wide and fused, both
ici tiers (`stacked=True` scans replica 0), and the paged table's
physical frames; the host-DRAM cold tier is scanned by the numpy
oracle below (runtime/engine.py, same pattern as the census host tier).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.ops.kernels import get_raw_kernels

I64 = jnp.int64

# Leaky buckets store their level in Q44.20 fixed point
# (models/bucket.py FIXED_SHIFT); whole tokens = remaining >> 20.
# Mirrored literal so the metrics catalog can size its `le` bounds
# without importing jax (same convention as census.CENSUS_BUCKETS).
ADMISSION_SHIFT = 20
ADMISSION_BUCKETS = 32  # log2 hit bins: bin 31 is >= 2^30 excess hits

_OVER_LIMIT = 1  # api.types.Status.OVER_LIMIT (int8 column value)


class AdmissionOutput(NamedTuple):
    """O(buckets) device arrays from one admission scan."""

    keys: jnp.ndarray  # () int64 active keys (used, limit>0, unexpired)
    admitted_sum: jnp.ndarray  # () int64 Σ clamp(limit - tokens, >= 0)
    limit_sum: jnp.ndarray  # () int64 Σ limit over active keys
    excess_sum: jnp.ndarray  # () int64 Σ max(0, admitted - limit)
    excess_keys: jnp.ndarray  # () int64 active keys with excess > 0
    max_excess: jnp.ndarray  # () int64 worst single-key excess
    over_limit_keys: jnp.ndarray  # () int64 active keys at OVER_LIMIT
    excess_hist: jnp.ndarray  # (n_buckets,) int64 log2 bins of excess


def _admission_wide(wide, now, *, n_buckets: int) -> AdmissionOutput:
    active = wide.used & (wide.limit > 0) & (wide.expire_at > now)
    # Whole tokens remaining: raw column for token buckets, Q44.20
    # arithmetic shift for leaky (floors toward -inf, matching the
    # reference's truncation of non-negative levels and keeping debt
    # monotone for negative ones).
    tokens = jnp.where(
        wide.algo == jnp.int8(1),
        wide.remaining >> ADMISSION_SHIFT,
        wide.remaining,
    )
    admitted = jnp.where(
        active, jnp.maximum(wide.limit - tokens, jnp.int64(0)), jnp.int64(0)
    )
    excess = jnp.maximum(admitted - wide.limit, jnp.int64(0))

    keys = jnp.sum(active, dtype=I64)
    admitted_sum = jnp.sum(admitted, dtype=I64)
    limit_sum = jnp.sum(jnp.where(active, wide.limit, jnp.int64(0)), dtype=I64)
    excess_sum = jnp.sum(excess, dtype=I64)
    excess_mask = active & (excess > 0)
    excess_keys = jnp.sum(excess_mask, dtype=I64)
    max_excess = jnp.max(excess)
    over_limit_keys = jnp.sum(
        active & (wide.status == jnp.int8(_OVER_LIMIT)), dtype=I64
    )

    # Histogram of per-key excess over keys WITH excess (bin 0 would
    # otherwise just mirror `keys`); same boundary vector semantics as
    # census._log2_bins: bin 0 is < 1 hit (empty by construction here),
    # bin i is [2^(i-1), 2^i), the last bin absorbs the tail.
    bounds = jnp.int64(2) ** jnp.arange(n_buckets - 1, dtype=I64)
    idx = jnp.searchsorted(bounds, jnp.where(excess_mask, excess, 0), "right")
    ones = jnp.where(excess_mask, jnp.int64(1), jnp.int64(0))
    excess_hist = jnp.zeros((n_buckets,), dtype=I64).at[idx].add(ones)

    return AdmissionOutput(
        keys=keys,
        admitted_sum=admitted_sum,
        limit_sum=limit_sum,
        excess_sum=excess_sum,
        excess_keys=excess_keys,
        max_excess=max_excess,
        over_limit_keys=over_limit_keys,
        excess_hist=excess_hist,
    )


@functools.lru_cache(maxsize=None)
def make_admission(
    layout: str,
    ways: int,
    n_buckets: int = ADMISSION_BUCKETS,
    stacked: bool = False,
):
    """One jitted admission program: (table, now) -> AdmissionOutput.

    NON-donating by construction (plain jax.jit, no donate_argnums):
    the engine dispatches it on the live table reference between
    flushes, and the table must survive. `stacked=True` builds the
    replica-tier variant whose input leaves carry a leading device
    axis; it scans replica 0 (post-sync replicas are mirrors)."""
    RK = get_raw_kernels(layout)

    def impl(table, now):
        if stacked:
            table = jax.tree.map(lambda x: x[0], table)
        with jax.named_scope("admission.layout_in"):  # profile metadata
            wide = RK.to_wide(table)
        with jax.named_scope("admission.scan"):
            return _admission_wide(wide, now, n_buckets=n_buckets)

    return jax.jit(impl)


# ---------------------------------------------------------------------------
# Pure-numpy oracle (tests/test_admission.py + the kernel-fuzz section
# pin bit-exactness; runtime/engine.py runs it over the paged host tier)


def admission_oracle(
    wide, now: int, *, n_buckets: int = ADMISSION_BUCKETS
) -> dict:
    """Reference admission accounting over a WIDE table of host numpy
    arrays; mirrors _admission_wide decision-for-decision (same clamps,
    same arithmetic shift, same searchsorted boundaries)."""
    def h(col, dt):
        return np.asarray(col, dtype=dt)  # guberlint: allow-host-sync -- pure-numpy oracle over host reference arrays (differential target + paged host tier, never a device readback)

    used = h(wide.used, bool)
    algo = h(wide.algo, np.int8)
    status = h(wide.status, np.int8)
    limit = h(wide.limit, np.int64)
    remaining = h(wide.remaining, np.int64)
    expire_at = h(wide.expire_at, np.int64)

    active = used & (limit > 0) & (expire_at > np.int64(now))
    tokens = np.where(algo == 1, remaining >> ADMISSION_SHIFT, remaining)
    admitted = np.where(active, np.maximum(limit - tokens, 0), 0).astype(
        np.int64
    )
    excess = np.maximum(admitted - limit, 0).astype(np.int64)
    excess_mask = active & (excess > 0)

    bounds = np.int64(2) ** np.arange(n_buckets - 1, dtype=np.int64)
    idx = np.searchsorted(bounds, np.where(excess_mask, excess, 0), "right")
    excess_hist = np.bincount(
        idx[excess_mask], minlength=n_buckets
    ).astype(np.int64)

    return {
        "keys": int(active.sum()),
        "admitted_sum": int(admitted.sum()),
        "limit_sum": int(np.where(active, limit, 0).sum()),
        "excess_sum": int(excess.sum()),
        "excess_keys": int(excess_mask.sum()),
        "max_excess": int(excess.max(initial=0)),
        "over_limit_keys": int((active & (status == _OVER_LIMIT)).sum()),
        "excess_hist": excess_hist,
    }
