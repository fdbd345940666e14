"""Fused slot-table layout: ONE (N, C) tensor, one gather, one scatter.

Round-3 profiling showed the multi-column SoA kernels lose 2+ orders of
magnitude at large tables: XLA (CPU at least) fails to elide defensive
whole-table copies when many same-buffer gather->scatter column chains
are composed in one program — per-step cost became linear in TABLE size
(the 10M-key collapse: 341ms/batch at 16M slots where the constituent
gathers/scatters each cost ~1ms). Fusing every column into a single
(N, C) int64 tensor reduces the program to ONE row-block gather
(B, W, C) and ONE row scatter (B, C): 3.6ms/batch at 16M slots on the
same machine, ~95x faster, and per-step cost is once again O(batch), not
O(table).

This shape is also what a TPU wants: a group's W x C block is contiguous
in HBM, so the probe is a coalesced DMA stream rather than W x C strided
loads; the chosen way's state needs NO second gather (it is a slice of
the already-fetched block); and the scatter writes one contiguous row
per lane.

Columns (all int64; META packs lru<<4 | status<<2 | algo<<1 | used, as
in ops/packed.py):

  KHI KLO META EXP LIM DUR REM STM BUR INV

Branch semantics are bit-exact with the wide kernel: _token_paths /
_leaky_paths from ops/decide.py are reused verbatim, and the layout runs
the full oracle fuzz (tests/test_kernel_fuzz.py). Bucket field contract:
reference store.go:29-43; LRU/expiry policy: reference lrucache.go:98-118,
cache.go:43-57.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from gubernator_tpu.api.types import Algorithm, Behavior, Status
from gubernator_tpu.ops.decide import _leaky_paths, _token_paths
from gubernator_tpu.ops.layout import DecideOutput, RequestBatch, SlotTable

# The meta-word bit layout is a cross-layout contract (Loader snapshot
# interop): share packed.py's definition, never redeclare it.
from gubernator_tpu.ops.packed import (
    META_ALGO_SHIFT,
    META_LRU_SHIFT,
    META_STATUS_SHIFT,
    META_USED,
    _pack_meta,
)

I64 = jnp.int64

KHI, KLO, META, EXP, LIM, DUR, REM, STM, BUR, INV = range(10)
NCOLS = 10


class FusedTable(NamedTuple):
    """One (N, NCOLS) int64 tensor; a JAX pytree with a single leaf."""

    data: jnp.ndarray  # (N, NCOLS) int64

    @property
    def num_slots(self) -> int:
        return self.data.shape[0]

    # Wide-compatible host views (live_count, key pruning, tests).
    # `...` indexing so they also work on a device-stacked (D, N, C)
    # table (parallel/ici.py IciState).
    @property
    def used(self) -> jnp.ndarray:
        return (self.data[..., META] & META_USED) != 0

    @property
    def key_hi(self) -> jnp.ndarray:
        return self.data[..., KHI]

    @property
    def key_lo(self) -> jnp.ndarray:
        return self.data[..., KLO]

    @property
    def expire_at(self) -> jnp.ndarray:
        return self.data[..., EXP]

    @property
    def remaining(self) -> jnp.ndarray:
        return self.data[..., REM]

    @staticmethod
    def create(num_groups: int, ways: int = 8) -> "FusedTable":
        return FusedTable(
            data=jnp.zeros((num_groups * ways, NCOLS), dtype=jnp.int64)
        )


@jax.jit
def pack_table(wide: SlotTable) -> FusedTable:
    """Wide -> fused conversion (canonical snapshot interop)."""
    cols = [None] * NCOLS
    cols[KHI] = wide.key_hi
    cols[KLO] = wide.key_lo
    cols[META] = _pack_meta(wide.used, wide.algo, wide.status, wide.lru)
    cols[EXP] = wide.expire_at
    cols[LIM] = wide.limit
    cols[DUR] = wide.duration
    cols[REM] = wide.remaining
    cols[STM] = wide.stamp
    cols[BUR] = wide.burst
    cols[INV] = wide.invalid_at
    return FusedTable(data=jnp.stack(cols, axis=-1))


@jax.jit
def unpack_table(fused: FusedTable) -> SlotTable:
    d = fused.data
    meta = d[:, META]
    return SlotTable(
        key_hi=d[:, KHI],
        key_lo=d[:, KLO],
        used=(meta & META_USED) != 0,
        algo=((meta >> META_ALGO_SHIFT) & 1).astype(jnp.int8),
        status=((meta >> META_STATUS_SHIFT) & 3).astype(jnp.int8),
        limit=d[:, LIM],
        duration=d[:, DUR],
        remaining=d[:, REM],
        stamp=d[:, STM],
        expire_at=d[:, EXP],
        invalid_at=d[:, INV],
        burst=d[:, BUR],
        lru=meta >> META_LRU_SHIFT,
    )


def probe_ways(w_khi, w_klo, w_meta, w_exp, w_inv, batch, now):
    """Way-selection policy over per-way column arrays (each (B, W)):
    returns (exists, matched_way, insert_way, cat). Policy identical to
    the wide kernel's _choose_slot: matched-expired > empty > expired >
    LRU. Shared by the fused and narrow layouts so the two can never
    drift — narrow feeds it slices of its (B, W, C64) hot block."""
    w_used = (w_meta & META_USED) != 0
    w_lru = w_meta >> META_LRU_SHIFT
    w_expired = w_used & ((w_exp < now) | ((w_inv != 0) & (w_inv < now)))
    w_match = (
        w_used
        & (w_khi == batch.key_hi[:, None])
        & (w_klo == batch.key_lo[:, None])
    )
    live_match = w_match & ~w_expired
    exists = jnp.any(live_match, axis=1)
    matched_way = jnp.argmax(live_match, axis=1)

    cat = jnp.where(
        w_match & w_expired,
        0,
        jnp.where(~w_used, 1, jnp.where(w_expired, 2, 3)),
    ).astype(I64)
    way_off = jnp.arange(w_meta.shape[1], dtype=I64)[None, :]
    tie = jnp.where(cat == 3, jnp.clip(w_lru, 0, (1 << 44) - 1), way_off)
    score = (cat << 44) + tie
    insert_way = jnp.argmin(score, axis=1)
    return exists, matched_way, insert_way, cat


def _probe(rows, batch, now):
    """Way selection over a gathered (B, W, C) block (see probe_ways)."""
    return probe_ways(
        rows[..., KHI], rows[..., KLO], rows[..., META],
        rows[..., EXP], rows[..., INV], batch, now,
    )


def _decide_fused_impl(table: FusedTable, batch: RequestBatch, now, *, ways: int):
    # The scopes name the program's phases in a profile (xprof groups ops
    # by them); metadata only, the compiled arithmetic is the same.
    with jax.named_scope("layout_in"):
        now = jnp.asarray(now, dtype=I64)
        data = table.data
        n = data.shape[0]
        grp_base = batch.group.astype(I64) * ways
        way_ix = grp_base[:, None] + jnp.arange(ways, dtype=I64)[None, :]

        rows = data[way_ix]  # (B, W, C) — the ONE gather

    with jax.named_scope("probe"):
        exists, matched_way, insert_way, cat = _probe(rows, batch, now)

        way = jnp.where(exists, matched_way, insert_way)
        slot = grp_base + way
        st_row = jnp.take_along_axis(rows, way[:, None, None], axis=1)[:, 0]  # (B, C)

        pick = jax.vmap(lambda r, w: r[w])
        sel = pick(cat, insert_way)
        evicts_live = (~exists) & (sel == 3) & batch.active

        old_used = (st_row[:, META] & META_USED) != 0
        displaced = (
            batch.active
            & ~exists
            & old_used
            & (
                (st_row[:, KHI] != batch.key_hi)
                | (st_row[:, KLO] != batch.key_lo)
            )
        )
        evicted_hi = jnp.where(displaced, st_row[:, KHI], 0)
        evicted_lo = jnp.where(displaced, st_row[:, KLO], 0)

    with jax.named_scope("decide"):
        meta_sel = st_row[:, META]
        st = dict(
            algo=((meta_sel >> META_ALGO_SHIFT) & 1).astype(jnp.int8),
            status=((meta_sel >> META_STATUS_SHIFT) & 3).astype(jnp.int8),
            limit=st_row[:, LIM],
            duration=st_row[:, DUR],
            remaining=st_row[:, REM],
            stamp=st_row[:, STM],
            expire_at=st_row[:, EXP],
            burst=st_row[:, BUR],
            invalid_at=st_row[:, INV],
        )
        for k in st:
            st[k] = jnp.where(exists, st[k], jnp.zeros_like(st[k]))

        bhv = batch.behavior
        b_greg = (bhv & int(Behavior.DURATION_IS_GREGORIAN)) != 0
        b_reset = (bhv & int(Behavior.RESET_REMAINING)) != 0
        b_drain = (bhv & int(Behavior.DRAIN_OVER_LIMIT)) != 0

        tok_state, tok_resp = _token_paths(batch, st, b_greg, b_reset, b_drain, exists, now)
        lky_state, lky_resp = _leaky_paths(batch, st, b_greg, b_reset, b_drain, exists, now)

        is_leaky = batch.algo == jnp.int8(Algorithm.LEAKY_BUCKET)

        def both(t, l):
            return jnp.where(is_leaky, l, t)

        new_state = {k: both(tok_state[k], lky_state[k]) for k in tok_state}
        resp = {k: both(tok_resp[k], lky_resp[k]) for k in tok_resp}

    with jax.named_scope("scatter"):
        freed = ~new_state["used"]
        cols = [None] * NCOLS
        cols[KHI] = jnp.where(freed, 0, batch.key_hi)
        cols[KLO] = jnp.where(freed, 0, batch.key_lo)
        cols[META] = jnp.where(
            freed,
            0,
            _pack_meta(
                jnp.ones_like(freed),
                batch.algo,
                new_state["status"],
                jnp.broadcast_to(now, freed.shape),
            ),
        )
        cols[EXP] = new_state["expire_at"]
        cols[LIM] = new_state["limit"]
        cols[DUR] = new_state["duration"]
        cols[REM] = new_state["remaining"]
        cols[STM] = new_state["stamp"]
        cols[BUR] = new_state["burst"]
        # The store's invalidation mark survives updates on a live entry
        # (reference: algorithms never touch CacheItem.InvalidAt); fresh
        # inserts and freed slots clear it.
        cols[INV] = jnp.where(exists & ~freed, st["invalid_at"], 0)
        new_row = jnp.stack([c.astype(I64) for c in cols], axis=-1)  # (B, C)

        idx = jnp.where(batch.active, slot, n)
        new_data = data.at[idx].set(new_row, mode="drop")  # the ONE scatter

    with jax.named_scope("layout_out"):
        act = batch.active
        out = DecideOutput(
            status=jnp.where(act, resp["status"], jnp.int8(0)),
            limit=jnp.where(act, batch.limit, 0),
            remaining=jnp.where(act, resp["remaining"], 0),
            reset_time=jnp.where(act, resp["reset_time"], 0),
            slot=idx,
            evicted_hi=evicted_hi,
            evicted_lo=evicted_lo,
            freed=act & freed,
            hits=jnp.sum(act & exists),
            misses=jnp.sum(act & ~exists),
            unexpired_evictions=jnp.sum(evicts_live),
            over_limit=jnp.sum(act & resp["over"]),
        )

    return FusedTable(data=new_data), out


@functools.partial(jax.jit, static_argnames=("ways",), donate_argnums=(0,))
def decide_fused(table: FusedTable, batch: RequestBatch, now, ways: int = 8):
    return _decide_fused_impl(table, batch, now, ways=ways)


@functools.partial(jax.jit, static_argnames=("ways",), donate_argnums=(0,))
def decide_scan_fused(table: FusedTable, batches: RequestBatch, nows, ways: int = 8):
    def step(tbl, xs):
        b, now = xs
        tbl, out = _decide_fused_impl(tbl, b, now, ways=ways)
        return tbl, out

    return jax.lax.scan(step, table, (batches, nows))


@functools.partial(jax.jit, static_argnames=("ways",))
def probe_exists_fused(table: FusedTable, key_hi, key_lo, group, now, ways: int = 8):
    """Residency probe (store read-through seam), fused layout."""
    now = jnp.asarray(now, dtype=I64)
    grp_base = group.astype(I64) * ways
    way_ix = grp_base[:, None] + jnp.arange(ways, dtype=I64)[None, :]
    rows = table.data[way_ix]
    w_meta = rows[..., META]
    w_used = (w_meta & META_USED) != 0
    w_invalid = rows[..., INV]
    w_expired = w_used & (
        (rows[..., EXP] < now) | ((w_invalid != 0) & (w_invalid < now))
    )
    live = (
        w_used
        & ~w_expired
        & (rows[..., KHI] == key_hi[:, None])
        & (rows[..., KLO] == key_lo[:, None])
    )
    return jnp.any(live, axis=1)


@jax.jit
def gather_rows_fused(table: FusedTable, slots) -> SlotTable:
    """Post-decide row readback, expanded to the wide row struct so the
    engine's store write-behind code is layout-agnostic."""
    n = table.num_slots
    safe = jnp.clip(slots, 0, n - 1)
    valid = slots < n
    rows = jnp.where(valid[:, None], table.data[safe], 0)  # (B, C)
    meta = rows[:, META]
    return SlotTable(
        key_hi=rows[:, KHI],
        key_lo=rows[:, KLO],
        used=(meta & META_USED) != 0,
        algo=((meta >> META_ALGO_SHIFT) & 1).astype(jnp.int8),
        status=((meta >> META_STATUS_SHIFT) & 3).astype(jnp.int8),
        limit=rows[:, LIM],
        duration=rows[:, DUR],
        remaining=rows[:, REM],
        stamp=rows[:, STM],
        expire_at=rows[:, EXP],
        invalid_at=rows[:, INV],
        burst=rows[:, BUR],
        lru=meta >> META_LRU_SHIFT,
    )


def _inject_fused_impl(table: FusedTable, items, now, ways: int):
    now = jnp.asarray(now, dtype=I64)
    data = table.data
    n = data.shape[0]
    batch_like = RequestBatch.zeros(items.key_hi.shape[0])._replace(
        key_hi=items.key_hi,
        key_lo=items.key_lo,
        group=items.group,
        active=items.active,
    )
    grp_base = batch_like.group.astype(I64) * ways
    way_ix = grp_base[:, None] + jnp.arange(ways, dtype=I64)[None, :]
    rows = data[way_ix]
    exists, matched_way, insert_way, _cat = _probe(rows, batch_like, now)
    way = jnp.where(exists, matched_way, insert_way)
    slot = grp_base + way
    st_row = jnp.take_along_axis(rows, way[:, None, None], axis=1)[:, 0]
    old_used = (st_row[:, META] & META_USED) != 0
    displaced = (
        items.active
        & ~exists
        & old_used
        & ((st_row[:, KHI] != items.key_hi) | (st_row[:, KLO] != items.key_lo))
    )
    evicted_hi = jnp.where(displaced, st_row[:, KHI], 0)
    evicted_lo = jnp.where(displaced, st_row[:, KLO], 0)

    cols = [None] * NCOLS
    cols[KHI] = items.key_hi
    cols[KLO] = items.key_lo
    cols[META] = _pack_meta(
        jnp.ones_like(items.active),
        items.algo,
        items.status,
        jnp.broadcast_to(now, items.key_hi.shape),
    )
    cols[EXP] = items.expire_at
    cols[LIM] = items.limit
    cols[DUR] = items.duration
    cols[REM] = items.remaining
    cols[STM] = items.stamp
    cols[BUR] = items.burst
    cols[INV] = items.invalid_at
    new_row = jnp.stack([c.astype(I64) for c in cols], axis=-1)
    idx = jnp.where(items.active, slot, n)
    return (
        FusedTable(data=data.at[idx].set(new_row, mode="drop")),
        evicted_hi,
        evicted_lo,
    )


@functools.partial(jax.jit, static_argnames=("ways",), donate_argnums=(0,))
def inject_fused(table: FusedTable, items, now, ways: int = 8):
    return _inject_fused_impl(table, items, now, ways)
