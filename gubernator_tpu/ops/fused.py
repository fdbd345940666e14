"""Fused slot-table layout: ONE tensor of 32-bit words, one gather, one scatter.

A decide program reads the lanes' slots (B x W of them), computes on
int64, and writes one slot a lane: its cost has to follow the lanes, not
the table. The table is therefore what the device can index in place.

A TPU has no 64-bit lanes. XLA rewrites every s64 value into two u32
values and converts a whole int64 *parameter* with `X64SplitLow` /
`X64SplitHigh` and a whole int64 *result* with `X64Combine`: with the
table as one (N, C) int64 array, each dispatch read and wrote the table
three times over to touch a few kilobytes (3.1 ms of a 3.1 ms program at
2,097,152 slots on a v5e; PERF.md §6, PR 29). So the table is stored as
the words themselves, and only the gathered (B, W, C) block and the
(B, C) result row are widened (`join` / `split`: `(hi << 32) | lo`,
exact for every int64).

The words' arrangement is the one the TPU indexes natively. A slot is
SLOT_WORDS = 32 words: its NCOLS low words, its NCOLS high words, 12 of
padding (128 B, what the int64 table took too: `s64[N, 10]` sat at 16
sublanes of 8 B). LINE_SLOTS = 8 consecutive slots make a line, and the
table is (N / 8, 256) uint32: a minor dimension of two whole 128-lane
tiles, which the device keeps row-major. A program gathers whole lines
by their row index and scatter-adds whole lines (`read_windows`,
`add_windows`): rows are what the device gathers and scatters natively,
at every table size, in place on the donated buffer. A lane's slot moves
by `new - old` modulo 2**32 and the rest of its line by zero, so lanes
whose slots share a line (two 4-way groups) still add up to both.

What was tried and measured on the chip (PERF.md §6, PR 29): `(N, 20)`
words, two `(N, 10)` halves and `(20, N)` planes all get the slot axis as
the minor one (20 words pad to 24 sublanes); for a table of up to 262,144
slots the compiler then re-lays-out the whole table around the gather
and back after the scatter (two table-sized copies a dispatch), and the
replica tier's (1, N, 20) shard gets a (1, 128) tiling and is copied
both ways at every size. Lines have neither: a (1, N / 8, 256) shard is
the flat table's own tiling. A gather or scatter of *windows inside* a
line (a slot's 32 words at a word offset) is run as a serial loop of
`dynamic-slice` / `dynamic-update-slice`, 1-3.5 us a window: hence
whole lines, and the window picked by mask and sum.

Whole-table reads (snapshots, the census, the admission scan, the sync
tick, the host views) go through `word` / `col` / `cols` and `_lines`,
column by column through the transposed table; they are conversions, off
the decide path. `pack_table` / `unpack_table` (`from_wide` / `to_wide`)
stay the interchange.

Columns (int64; META packs lru<<4 | status<<2 | algo<<1 | used):

  KHI KLO META EXP LIM DUR REM STM BUR INV

Branch semantics are bit-exact with the wide kernel: _both_paths from
ops/decide.py (the token path, and the leaky path where a wave holds a
leaky lane) is reused verbatim, and the layout runs
the full oracle fuzz (tests/test_kernel_fuzz.py). Bucket field contract:
reference store.go:29-43; LRU/expiry policy: reference lrucache.go:98-118,
cache.go:43-57.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from gubernator_tpu.api.types import Behavior
from gubernator_tpu.ops.decide import _both_paths
from gubernator_tpu.ops.layout import (
    BUR,
    DUR,
    EXP,
    INV,
    KHI,
    KLO,
    LIM,
    META,
    META_ALGO_SHIFT,
    META_LRU_SHIFT,
    META_STATUS_SHIFT,
    META_USED,
    NCOLS,
    REM,
    STM,
    DecideOutput,
    RequestBatch,
    SlotTable,
    gathered_rows,
    pack_meta as _pack_meta,
    packed_cols,
    probed_waves,
    wide_rows as _wide,
)

I64 = jnp.int64
U32 = jnp.uint32

NWORDS = 2 * NCOLS  # a slot's state: NCOLS low words, then NCOLS high words

# A slot takes SLOT_WORDS words of a line (its NWORDS and padding: 128 B,
# what the int64 table took at 16 sublanes of 8 B), and a line holds
# LINE_SLOTS consecutive slots: a whole number of 128-lane tiles, so
# the device keeps the table row-major and a slot is 128 contiguous
# bytes of HBM (see the module docstring).
SLOT_WORDS = 32
LINE_SLOTS = 8

_LOW = 0xFFFFFFFF


def join(lo, hi):
    """Two uint32 word arrays -> the int64 they spell, two's complement:
    `(hi << 32) | lo`, exact for every int64."""
    return (hi.astype(I64) << 32) | lo.astype(I64)


def split(x):
    """int64 -> (low, high) uint32 words, the inverse of `join`. Masked
    before the narrowing so that every converted value is in range."""
    x = x.astype(I64)
    return (x & _LOW).astype(U32), ((x >> 32) & _LOW).astype(U32)


def join_words(words):
    """(..., SLOT_WORDS) uint32 slot words -> (..., NCOLS) int64."""
    return join(words[..., :NCOLS], words[..., NCOLS:NWORDS])


def split_words(x):
    """(..., NCOLS) int64 -> (..., SLOT_WORDS) uint32 slot words, the
    padding zero."""
    lo, hi = split(x)
    pad = jnp.zeros(lo.shape[:-1] + (SLOT_WORDS - NWORDS,), dtype=U32)
    return jnp.concatenate([lo, hi, pad], axis=-1)


def _per_line(n: int) -> int:
    """Slots a line of an N-slot table holds: LINE_SLOTS, or for a table
    whose N is no multiple of it (tests' tiny ones) the most that
    divides N. The form is a function of N alone."""
    return math.gcd(n, LINE_SLOTS)


def _lines(words):
    """A list of SLOT_WORDS or fewer (..., N) uint32 word columns, in
    slot order (the rest is padding) -> the (..., N / per, per *
    SLOT_WORDS) lines. Column-wise, through the transposed table: a
    whole-table conversion never makes an array whose minor dimension is
    a slot's few words, which the device would pad to 128 lanes."""
    n = words[0].shape[-1]
    per = _per_line(n)
    lead = words[0].shape[:-1]
    zero = jnp.zeros(lead + (per, n // per), dtype=U32)
    planes = [  # word w of the slot at place k of line l: (..., per, L)
        jnp.swapaxes(w.astype(U32).reshape(lead + (n // per, per)), -1, -2)
        for w in words
    ] + [zero] * (SLOT_WORDS - len(words))
    t = jnp.stack(planes, axis=-2)  # (..., per, SLOT_WORDS, L)
    t = t.reshape(lead + (per * SLOT_WORDS, n // per))
    return jnp.swapaxes(t, -1, -2)


def _planes(data):
    """The table transposed: word w of the slot at place k of line l at
    [..., k, w, l]. The inverse of `_lines`, column-wise for the same
    reason."""
    lines, per = data.shape[-2], data.shape[-1] // SLOT_WORDS
    return jnp.swapaxes(data, -1, -2).reshape(
        data.shape[:-2] + (per, SLOT_WORDS, lines)
    )


def _slot_order(plane):
    """(..., per, L) -> (..., N): slot s is place s % per of line
    s // per."""
    return jnp.swapaxes(plane, -1, -2).reshape(
        plane.shape[:-2] + (plane.shape[-2] * plane.shape[-1],)
    )


# Jitted, so that a host view taken outside a program (the engine's
# warm-up probe, key pruning) costs one fused pass and not a copy of the
# table for each step of the conversion.
@functools.partial(jax.jit, static_argnums=(1,))
def _word(data, w: int):
    return _slot_order(_planes(data)[..., w, :])


@jax.jit
def _cols(data):
    t = _planes(data)
    return [
        join(_slot_order(t[..., c, :]), _slot_order(t[..., NCOLS + c, :]))
        for c in range(NCOLS)
    ]


class FusedTable(NamedTuple):
    """One (N / LINE_SLOTS, LINE_SLOTS * SLOT_WORDS) uint32 tensor of
    lines; slot s is words [(s % LINE_SLOTS) * SLOT_WORDS, +SLOT_WORDS)
    of line s // LINE_SLOTS. A JAX pytree with a single leaf, slot
    order along its first axis."""

    data: jnp.ndarray  # (N / LINE_SLOTS, LINE_SLOTS * SLOT_WORDS) uint32

    @property
    def num_slots(self) -> int:
        return self.data.shape[-2] * (self.data.shape[-1] // SLOT_WORDS)

    def word(self, w: int) -> jnp.ndarray:
        """Word `w` of every slot, (..., N) in slot order (a whole-table
        read: host views and conversions, never the decide path)."""
        return _word(self.data, w)

    def col(self, c: int) -> jnp.ndarray:
        """Column `c` of every slot as int64, (..., N)."""
        return join(self.word(c), self.word(NCOLS + c))

    def cols(self) -> list:
        return _cols(self.data)

    # Wide-compatible host views (live_count, key pruning, tests). They
    # also work on a device-stacked (D, ...) table (parallel/ici.py
    # IciState).
    @property
    def used(self) -> jnp.ndarray:
        return (self.word(META) & META_USED) != 0

    @property
    def key_hi(self) -> jnp.ndarray:
        return self.col(KHI)

    @property
    def key_lo(self) -> jnp.ndarray:
        return self.col(KLO)

    @property
    def expire_at(self) -> jnp.ndarray:
        return self.col(EXP)

    @property
    def remaining(self) -> jnp.ndarray:
        return self.col(REM)

    @staticmethod
    def create(num_groups: int, ways: int = 8) -> "FusedTable":
        n = num_groups * ways
        per = _per_line(n)
        return FusedTable(
            data=jnp.zeros((n // per, per * SLOT_WORDS), dtype=U32)
        )


@jax.jit
def pack_table(wide: SlotTable) -> FusedTable:
    """Wide -> fused conversion (canonical snapshot interop)."""
    lo, hi = zip(*(split(c) for c in packed_cols(wide)))
    return FusedTable(data=_lines(list(lo) + list(hi)))


@jax.jit
def unpack_table(fused: FusedTable) -> SlotTable:
    return _wide(fused.cols())


def probe_ways(w_khi, w_klo, w_meta, w_exp, w_inv, batch, now):
    """Way-selection policy over per-way column arrays (each (B, W)):
    returns (exists, matched_way, insert_way, cat). Policy identical to
    the wide kernel's _choose_slot: matched-expired > empty > expired >
    LRU."""
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


def _pick(blocks, which):
    """blocks (M, P, X), which (M,) in [0, P) -> blocks[m, which[m]],
    (M, X), by mask and sum: elementwise work on what was gathered (the
    TPU runs a gather of windows inside a row as a serial loop, one
    `dynamic-slice` a window: 1 us each, PERF.md §6, PR 29)."""
    if blocks.shape[1] == 1:
        return blocks[:, 0]
    ids = jnp.arange(blocks.shape[1], dtype=I64)
    sel = ids[None, :, None] == which[:, None, None]
    return jnp.where(sel, blocks, 0).sum(axis=1, dtype=blocks.dtype)


def _per(data) -> int:
    """Slots a line of `data` holds."""
    return data.shape[-1] // SLOT_WORDS


def read_windows(data, first, k: int):
    """The words of the `k` consecutive slots from slot `first` (M,),
    (M, k, SLOT_WORDS); `k` divides a line's slots and `first` is a
    multiple of it. The table is read by whole lines, the rows the
    device gathers natively, and the window picked from its line. A
    slot past the end reads the last line (ops/paged.py: a clamped row
    never matches the key)."""
    per = _per(data)
    lines = data[first // per].reshape(-1, per // k, k * SLOT_WORDS)
    return _pick(lines, (first % per) // k).reshape(-1, k, SLOT_WORDS)


def add_windows(data, first, delta):
    """`data` with `delta` (M, k, SLOT_WORDS) added, modulo 2**32, to the
    words of the `k` consecutive slots from slot `first` (M,) (`k` and
    `first` as in read_windows); a slot of N or more (an inactive lane)
    adds nothing. Whole lines again, zero outside the window: two
    windows of one line add up to both, so the lanes of a wave need not
    lie in different lines. In place on a donated table."""
    per = _per(data)
    m, k = delta.shape[:2]
    place = (
        jnp.arange(per // k, dtype=I64)[None, :, None]
        == ((first % per) // k)[:, None, None]
    )
    lines = jnp.where(place, delta.reshape(m, 1, k * SLOT_WORDS), 0)
    return data.at[first // per].add(
        lines.reshape(m, per * SLOT_WORDS), mode="drop"
    )


def _group_windows(data, group, ways: int):
    """(first, k): the windows of `k` slots from slots `first` that make
    up groups `group` (B,), in order. One window a group where a line
    holds whole groups, else (tests' odd geometries) one a slot."""
    grp_base = group.astype(I64) * ways
    if _per(data) % ways == 0:
        return grp_base, ways
    way_ix = grp_base[:, None] + jnp.arange(ways, dtype=I64)[None, :]
    return way_ix.reshape(-1), 1


def _group_words(data, group, ways: int):
    """The words of every slot of groups `group` (B,): (B, W,
    SLOT_WORDS)."""
    first, k = _group_windows(data, group, ways)
    return read_windows(data, first, k).reshape(-1, ways, SLOT_WORDS)


def _gather_groups(data, group, ways: int):
    """The rows of groups `group` (B,), widened to int64: (B, W, NCOLS).
    The only place a decide program reads the table, and what it reads
    is the lanes' lines."""
    return join_words(_group_words(data, group, ways))


def _scatter(data, idx, rows, old=None):
    """Write (B, NCOLS) int64 `rows` at slots `idx` (B,), which hold
    `old` (read here if not given): each slot's words move by new - old
    (see add_windows)."""
    if old is None:
        old = join_words(read_windows(data, idx, 1)[:, 0])
    delta = split_words(rows) - split_words(old)
    return add_windows(data, idx, delta[:, None, :])


def take_groups(table: FusedTable, gids, ways: int) -> FusedTable:
    """The table of groups `gids` (C,) alone, in that order: lines of one
    group each. An index past the end reads slots of the last line."""
    words = _group_words(table.data, gids, ways)
    return FusedTable(data=words.reshape(-1, ways * SLOT_WORDS))


def put_groups(
    table: FusedTable, gids, ways: int, part: FusedTable
) -> FusedTable:
    """`table` with the groups of `part` (whatever its line width)
    written back at `gids`; a group past the end writes nothing."""
    data = table.data
    new = part.data.reshape(-1, ways, SLOT_WORDS)
    delta = new - _group_words(data, gids, ways)
    first, k = _group_windows(data, gids, ways)
    return FusedTable(
        data=add_windows(data, first, delta.reshape(-1, k, SLOT_WORDS))
    )


def _mix32(x):
    """murmur3's 32-bit finalizer (elementwise, uint32): the avalanche
    of the sync tick's content fingerprints. A bijection, and arithmetic
    the device has lanes for."""
    x = (x ^ (x >> 16)) * U32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * U32(0xC2B2AE35)
    return x ^ (x >> 16)


def _any_of_group(flags, ways: int):
    """(N,) bool -> (G,) bool: whether any of a group's `ways`
    consecutive flags holds. 128 lanes hold 128 / ways whole groups, and
    a 0/1 matrix on the matrix unit sums each group's lanes (exact: 0
    and 1 in bfloat16, the sums in float32). A reshape to (G, ways)
    makes the device pad `ways` to 128 lanes: 0.4 ms a plane of
    1,048,576 slots on a v5e, against 0.07 (PERF.md §6, PR 31). Tables
    that do not divide so (tests' tiny ones) take the reshape."""
    if flags.shape[0] % 128 or 128 % ways:
        return flags.reshape(-1, ways).any(axis=1)
    group_of_lane = jnp.arange(128, dtype=jnp.int32) // ways
    pick = (
        group_of_lane[:, None]
        == jnp.arange(128 // ways, dtype=jnp.int32)[None, :]
    )
    held = jnp.dot(
        flags.reshape(-1, 128).astype(jnp.bfloat16),
        pick.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )
    return (held > 0).reshape(-1)


# 32-bit accumulators of a group's content fingerprint: 128 bits, what the
# generic walk's two uint64 sums hold (parallel/ici.py).
FINGERPRINT_WORDS = 4


def group_signals(table: FusedTable, pending, now, ways: int):
    """What the capped sync tick selects groups by (parallel/ici.py
    make_sync_step), from one read of the table as it lies: a group is
    `ways * SLOT_WORDS` consecutive words of the lines, so the table
    reshapes to one row a group, and every output is a sum over a row's
    lanes in 32-bit arithmetic. `pending` is the (2, N) uint32 hit
    deltas (low words, high words). Returns

    - (FINGERPRINT_WORDS, G) int32 content fingerprints: independently
      salted sums over the group's words, each word mixed with a salt
      of its place in the group (way and word) and of the accumulator,
      so the same keys at other ways read differently. int32 because
      the chips compare them by a psum. `pending` is not in them: where
      any of it is set the next flag holds, and where none is, it is
      the same zeros on every chip;
    - (G,) bool: some slot of the group has hits pending;
    - (G,) bool: some slot of the group is used and expired by `now`
      (the full merge would erase it, though it reads the same on every
      chip). A slot's three facts sit in three lanes (META's used bit,
      EXP's two words against `now`'s), so each lane contributes its
      bits to its way's nibble of one more sum (up to 8 ways a sum), and
      the nibbles are judged group by group: no lane meets another but
      in a sum."""
    g = table.num_slots // ways
    width = ways * SLOT_WORDS
    # Behind a barrier: left free, the compiler may move the lane-wise
    # work before the reshape and re-lay-out each of its results, three
    # copies of the table where this is one (seen for a one-device mesh).
    rows = jax.lax.optimization_barrier(table.data.reshape(g, width))
    place = jnp.arange(width, dtype=U32)
    word, way = place % SLOT_WORDS, place // SLOT_WORDS
    fps = []
    for a in range(1, FINGERPRINT_WORDS + 1):
        salt = _mix32(place * U32(0x9E3779B9) + U32((0x7F4A7C15 * a) & _LOW))
        fps.append(_mix32(rows ^ salt).sum(axis=1, dtype=U32))

    now_lo, now_hi = split(now)

    def signed(x):
        return jax.lax.bitcast_convert_type(x, jnp.int32)

    # used: 1, EXP's low word below now's: 2, high word below: 4, equal: 8
    facts = jnp.where(
        word == META,
        rows & META_USED,
        jnp.where(
            word == EXP,
            (rows < now_lo).astype(U32) << 1,
            jnp.where(
                word == NCOLS + EXP,
                (signed(rows) < signed(now_hi)).astype(U32) << 2
                | (rows == now_hi).astype(U32) << 3,
                0,
            ),
        ),
    )
    expired = jnp.zeros(g, dtype=bool)
    for first in range(0, ways, 8):
        mine = (way >= first) & (way < first + 8)
        nibbles = jnp.where(mine, facts << (4 * (way % 8)), 0).sum(
            axis=1, dtype=U32
        )
        for w in range(min(8, ways - first)):
            n = nibbles >> (4 * w)
            expired |= ((n & 1) != 0) & (
                ((n & 4) != 0) | (((n & 8) != 0) & ((n & 2) != 0))
            )
    return (
        jax.lax.bitcast_convert_type(jnp.stack(fps), jnp.int32),
        _any_of_group((pending[0] | pending[1]) != 0, ways),
        expired,
    )


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
        n = table.num_slots
        grp_base = batch.group.astype(I64) * ways

        rows = _gather_groups(data, batch.group, ways)  # (B, W, C) — the ONE gather

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

        new_state, resp = _both_paths(batch, st, b_greg, b_reset, b_drain, exists, now)

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
        new_data = _scatter(data, idx, new_row, st_row)  # the ONE scatter

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


def _probe_exists_fused_impl(table: FusedTable, batch, now, ways: int):
    rows = _gather_groups(table.data, batch.group, ways)
    w_meta = rows[..., META]
    w_used = (w_meta & META_USED) != 0
    w_invalid = rows[..., INV]
    w_expired = w_used & (
        (rows[..., EXP] < now) | ((w_invalid != 0) & (w_invalid < now))
    )
    live = (
        w_used
        & ~w_expired
        & (rows[..., KHI] == batch.key_hi[:, None])
        & (rows[..., KLO] == batch.key_lo[:, None])
    )
    return batch.active & jnp.any(live, axis=1)


@functools.partial(jax.jit, static_argnames=("ways",))
def probe_exists_fused(table: FusedTable, operand, ways: int = 8):
    """Residency probe (store read-through seam), fused layout, of the
    wave's own uploaded operand, or of a stacked run's, whole
    (ops/layout.py probed_waves)."""
    return probed_waves(
        lambda batch, now: _probe_exists_fused_impl(table, batch, now, ways),
        operand,
    )


def _gather_cols(table: FusedTable, safe):
    """The (NCOLS, B) packed columns of in-range slots `safe` (B,)."""
    return join_words(read_windows(table.data, safe, 1)[:, 0]).T


@functools.partial(jax.jit, static_argnames=("from_output",))
def gather_rows_fused(table: FusedTable, slots, from_output: bool = False):
    """Post-decide row readback: the lanes' rows as one packed
    (NCOLS, B) int64 array (ops/layout.py gathered_rows / wide_rows)."""
    return gathered_rows(
        functools.partial(_gather_cols, table),
        slots, table.num_slots, from_output,
    )


def _inject_fused_impl(table: FusedTable, items, now, ways: int):
    now = jnp.asarray(now, dtype=I64)
    data = table.data
    n = table.num_slots
    batch_like = RequestBatch.zeros(items.key_hi.shape[0])._replace(
        key_hi=items.key_hi,
        key_lo=items.key_lo,
        group=items.group,
        active=items.active,
    )
    grp_base = batch_like.group.astype(I64) * ways
    rows = _gather_groups(data, batch_like.group, ways)
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
        FusedTable(data=_scatter(data, idx, new_row, st_row)),
        evicted_hi,
        evicted_lo,
    )


@functools.partial(jax.jit, static_argnames=("ways",), donate_argnums=(0,))
def inject_fused(table: FusedTable, items, now, ways: int = 8):
    return _inject_fused_impl(table, items, now, ways)
