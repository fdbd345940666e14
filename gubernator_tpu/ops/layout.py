"""HBM slot table layout and batch operand structs.

The table replaces the reference's per-worker LRU caches + bucket structs
(reference lrucache.go:32-214, store.go:29-43, cache.go:29-41) with one
struct-of-arrays region designed for vectorized gather/scatter:

- W-way set-associative: a key's 128-bit hash picks a *group* of W
  contiguous slots; matching, insertion, and LRU eviction all happen
  inside the decide kernel over the W gathered candidates — no host
  round-trips (SURVEY.md §7 hard part (d)).
- Eviction policy is least-recently-used within the group, preferring
  expired slots, mirroring the reference cache's evict-oldest +
  lazy-expiry behavior (reference lrucache.go:98-100, 115-118) at group
  granularity.
- `remaining` holds whole tokens for TOKEN_BUCKET and Q44.20 fixed point
  for LEAKY_BUCKET (see models/bucket.py).
- `stamp` is TokenBucketItem.CreatedAt / LeakyBucketItem.UpdatedAt.
- `invalid_at` supports the Store plugin's re-fetch hint
  (reference cache.go:35-40).

All arrays are int64/bool; (key_hi, key_lo) == (0, 0) marks empty.

SlotTable is also the CANONICAL interchange row format: the serving
layout (ops/fused.py) converts to/from it
for Loader snapshots, the ici sync tick's merge, and store write-behind
rows, so on-disk state and cross-layer seams never depend on the
device-resident packing (ops/kernels.py to_wide/from_wide).
"""

from __future__ import annotations

import sys
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_WAYS = 8


class SlotTable(NamedTuple):
    """Struct-of-arrays counter table; a JAX pytree."""

    key_hi: jnp.ndarray  # (N,) int64
    key_lo: jnp.ndarray  # (N,) int64
    used: jnp.ndarray  # (N,) bool
    algo: jnp.ndarray  # (N,) int8
    status: jnp.ndarray  # (N,) int8 (token-bucket sticky status)
    limit: jnp.ndarray  # (N,) int64
    duration: jnp.ndarray  # (N,) int64
    remaining: jnp.ndarray  # (N,) int64 (token: tokens; leaky: Q44.20)
    stamp: jnp.ndarray  # (N,) int64 (token: created_at; leaky: updated_at)
    expire_at: jnp.ndarray  # (N,) int64 epoch ms
    invalid_at: jnp.ndarray  # (N,) int64 epoch ms, 0 = unset
    burst: jnp.ndarray  # (N,) int64 (leaky only)
    lru: jnp.ndarray  # (N,) int64 last-access epoch ms

    @property
    def num_slots(self) -> int:
        return self.key_hi.shape[0]

    @staticmethod
    def create(num_groups: int, ways: int = DEFAULT_WAYS) -> "SlotTable":
        n = num_groups * ways
        i64 = lambda: jnp.zeros((n,), dtype=jnp.int64)  # noqa: E731
        return SlotTable(
            key_hi=i64(),
            key_lo=i64(),
            used=jnp.zeros((n,), dtype=bool),
            algo=jnp.zeros((n,), dtype=jnp.int8),
            status=jnp.zeros((n,), dtype=jnp.int8),
            limit=i64(),
            duration=i64(),
            remaining=i64(),
            stamp=i64(),
            expire_at=i64(),
            invalid_at=i64(),
            burst=i64(),
            lru=i64(),
        )


class RequestBatch(NamedTuple):
    """One wave's operands as the decide program sees them inside its jit
    (unpack_operand below), padded to a fixed batch size; on the host,
    views of the wave's one WaveOperand buffer.

    Host-resolved fields (the kernel is calendar/string-free):
    - key_hi/key_lo: 128-bit key hash (api/keys.py)
    - group: key's slot-group index (key_lo mod num_groups)
    - rate_num: leaky rate numerator — duration, or the full Gregorian
      interval under DURATION_IS_GREGORIAN (reference algorithms.go:336,349-351)
    - eff_duration: effective duration — duration, or time to end of the
      Gregorian interval (reference algorithms.go:353, 449)
    - greg_expire: gregorian_expiration(now), or 0 when not Gregorian

    Invariant the assembler maintains: within one batch, all active lanes
    have distinct `group` values (duplicate keys and group collisions go to
    subsequent waves), so scatters never collide and per-key request order
    is preserved across waves.
    """

    key_hi: jnp.ndarray  # (B,) int64
    key_lo: jnp.ndarray  # (B,) int64
    group: jnp.ndarray  # (B,) int32
    algo: jnp.ndarray  # (B,) int8
    behavior: jnp.ndarray  # (B,) int32 bit flags
    hits: jnp.ndarray  # (B,) int64
    limit: jnp.ndarray  # (B,) int64
    duration: jnp.ndarray  # (B,) int64 (raw request field)
    rate_num: jnp.ndarray  # (B,) int64
    eff_duration: jnp.ndarray  # (B,) int64
    greg_expire: jnp.ndarray  # (B,) int64
    burst: jnp.ndarray  # (B,) int64 (leaky: 0 already replaced by limit)
    created_at: jnp.ndarray  # (B,) int64 epoch ms
    active: jnp.ndarray  # (B,) bool padding mask

    @property
    def batch_size(self) -> int:
        return self.key_hi.shape[0]

    @staticmethod
    def zeros(b: int) -> "RequestBatch":
        """Host batch whose fields are views of one WaveOperand buffer."""
        return WaveOperand.zeros(b).batch


# ---- the decide program's interface: one operand in, one array out ----------
#
# A wave crosses the host-device boundary once each way. The host builds
# ONE int64 buffer of OPERAND_ROWS x B (WaveOperand below; RequestBatch's
# host fields are views of it), uploads it, and the program unpacks it
# to a RequestBatch inside the jit (unpack_operand) and packs its
# DecideOutput to one int64 vector (pack_output) that the host reads in
# one go (split_output). The per-field structs live only inside the
# compiled program. A RUN of waves of one width crosses once each way
# too: the buffers stacked to (W, OPERAND_ROWS, B) in, the vectors
# stacked to (W, rows * B + 4) out, the waves applied in order inside
# the one program (packed_waves).
#
# Rows: ten int64 fields, then two shared words (group | behavior << 32;
# algo | active << 8), the replica tier's per-lane home device, and `now`
# (lane 0).
_OP_I64 = (
    "key_hi", "key_lo", "hits", "limit", "duration", "rate_num",
    "eff_duration", "greg_expire", "burst", "created_at",
)
OP_GROUP_BEHAVIOR = len(_OP_I64)
OP_ALGO_ACTIVE = OP_GROUP_BEHAVIOR + 1
OP_HOME = OP_ALGO_ACTIVE + 1
OP_NOW = OP_HOME + 1
OPERAND_ROWS = OP_NOW + 1

# Where a narrow field sits inside its shared int64 word, as an element
# offset of the word's int32 / int8 view.
_LITTLE = sys.byteorder == "little"
_GROUP_AT, _BEHAVIOR_AT = (0, 1) if _LITTLE else (1, 0)
_ALGO_AT, _ACTIVE_AT = (0, 1) if _LITTLE else (7, 6)

# Output vector: OUT_LANE_ROWS (or OUT_STORE_ROWS with_store) rows of B
# lanes, then the four totals.
OUT_STATUS, OUT_LIMIT, OUT_REMAINING, OUT_RESET_TIME = range(4)
OUT_SLOT, OUT_EVICTED_HI, OUT_EVICTED_LO, OUT_FREED = range(4, 8)
OUT_LANE_ROWS = 4
OUT_STORE_ROWS = 8
OUT_TOTALS = 4  # hits, misses, unexpired_evictions, over_limit


# ---- the packed row: what gather_rows hands to the host ---------------------
#
# A slot's state as NCOLS int64 columns, the narrow fields sharing the
# META word (lru_stamp_ms << 4 | status << 2 | algo << 1 | used). These
# are the fused table's own columns (ops/fused.py) and the ONE array,
# (NCOLS, B), that every kernel set's gather_rows returns: a wave's rows
# cross to the host in one read, 80 B a lane. packed_cols / wide_rows
# convert from and to the wide struct on either side of the boundary
# (they use operators only: numpy arrays in, numpy arrays out).
KHI, KLO, META, EXP, LIM, DUR, REM, STM, BUR, INV = range(10)
NCOLS = 10
META_USED = 1
META_ALGO_SHIFT = 1
META_STATUS_SHIFT = 2
META_LRU_SHIFT = 4


def pack_meta(used, algo, status, lru):
    return (
        (lru.astype(np.int64) << META_LRU_SHIFT)
        | (status.astype(np.int64) & 3) << META_STATUS_SHIFT
        | (algo.astype(np.int64) & 1) << META_ALGO_SHIFT
        | used.astype(np.int64)
    )


def packed_cols(wide: SlotTable) -> list:
    """The wide struct's NCOLS int64 columns, in column order."""
    cols = [None] * NCOLS
    cols[KHI] = wide.key_hi
    cols[KLO] = wide.key_lo
    cols[META] = pack_meta(wide.used, wide.algo, wide.status, wide.lru)
    cols[EXP] = wide.expire_at
    cols[LIM] = wide.limit
    cols[DUR] = wide.duration
    cols[REM] = wide.remaining
    cols[STM] = wide.stamp
    cols[BUR] = wide.burst
    cols[INV] = wide.invalid_at
    return cols


def wide_rows(cols) -> SlotTable:
    """NCOLS int64 columns (a list, or the rows of one (NCOLS, ...)
    array, on the device or on the host) -> the wide struct. Of the one
    np.ndarray that gather_rows' read gives, this is THE host view: the
    int64 fields are views of it, the META fields small arrays."""
    meta = cols[META]
    return SlotTable(
        key_hi=cols[KHI],
        key_lo=cols[KLO],
        used=(meta & META_USED) != 0,
        algo=((meta >> META_ALGO_SHIFT) & 1).astype(np.int8),
        status=((meta >> META_STATUS_SHIFT) & 3).astype(np.int8),
        limit=cols[LIM],
        duration=cols[DUR],
        remaining=cols[REM],
        stamp=cols[STM],
        expire_at=cols[EXP],
        invalid_at=cols[INV],
        burst=cols[BUR],
        lru=meta >> META_LRU_SHIFT,
    )


def output_slots(vec):
    """Row OUT_SLOT of the vector a `with_store` decide produced, inside
    a jit: the slot each lane's row was written to, (B,); of a stacked
    run's (W, L) output, every wave's, (W, B)."""
    rows = vec[..., :-OUT_TOTALS].reshape(vec.shape[:-1] + (OUT_STORE_ROWS, -1))
    return rows[..., OUT_SLOT, :]


def rows_of_slots(gather, slots):
    """`gather(flat slots) -> (NCOLS, lanes)` over `slots` (B,) or a
    run's (W, B) as ONE gather of every lane: (NCOLS, B), or a run's
    (W, NCOLS, B), one packed array a wave."""
    rows = gather(slots.reshape(-1))
    return jnp.moveaxis(rows.reshape((NCOLS,) + slots.shape), 0, -2)


def gathered_rows(gather, slots, num_slots: int, from_output: bool):
    """THE body of every layout's gather_rows, inside its jit: `slots`
    is (B,) int64, or with `from_output` the vector a `with_store`
    decide just produced, whose OUT_SLOT row is taken here so that the
    slot column never leaves the device. `gather(safe)` gives the
    (NCOLS, B) columns of in-range slots; a slot past the table (a
    padding lane's) reads zeros. Returns the packed rows, (NCOLS, B)
    int64. A stacked run's (W, L) output gives (W, NCOLS, B): every
    wave's lanes in the one gather, read from the table as the run's
    last wave left it (a padding wave's rows are never looked at)."""
    if from_output:
        slots = output_slots(slots)

    def safe_rows(flat):
        rows = gather(jnp.clip(flat, 0, num_slots - 1))
        return jnp.where((flat < num_slots)[None, :], rows, 0)

    return rows_of_slots(safe_rows, slots)


class WaveOperand:
    """The host side of one wave (buf (OPERAND_ROWS, B)) or of W stacked
    waves (buf (W, OPERAND_ROWS, B)): the buffer that is uploaded, its
    RequestBatch fields as views with their own dtypes, and the replica
    tier's `home` row."""

    __slots__ = ("buf", "_batch")

    def __init__(self, buf: np.ndarray):
        self.buf = buf
        self._batch = None

    @property
    def batch(self) -> RequestBatch:
        """The buffer's fields as views (built on first use: a wave
        sliced off a stacked operand only to be uploaded never needs
        them)."""
        if self._batch is None:
            buf = self.buf
            gb = buf[..., OP_GROUP_BEHAVIOR, :].view(np.int32)
            aa = buf[..., OP_ALGO_ACTIVE, :]
            self._batch = RequestBatch(
                group=gb[..., _GROUP_AT::2],
                behavior=gb[..., _BEHAVIOR_AT::2],
                algo=aa.view(np.int8)[..., _ALGO_AT::8],
                active=aa.view(np.bool_)[..., _ACTIVE_AT::8],
                **{f: buf[..., i, :] for i, f in enumerate(_OP_I64)},
            )
        return self._batch

    @property
    def home(self) -> np.ndarray:
        return self.buf[..., OP_HOME, :]

    @staticmethod
    def zeros(b: int, waves: Optional[int] = None) -> "WaveOperand":
        shape = (OPERAND_ROWS, b) if waves is None else (waves, OPERAND_ROWS, b)
        return WaveOperand(np.zeros(shape, dtype=np.int64))

    @staticmethod
    def of(batch: RequestBatch, now: int, home=None) -> "WaveOperand":
        """Operand holding a copy of `batch` (any RequestBatch of host
        arrays), stamped: the tests' and tools' way in."""
        op = WaveOperand.zeros(batch.key_hi.shape[-1])
        for dst, src in zip(op.batch, batch):
            dst[...] = src
        if home is not None:
            op.home[...] = home
        return op.stamp(now)

    @property
    def lanes(self) -> int:
        return self.buf.shape[-1]

    def wave(self, w: int) -> "WaveOperand":
        return WaveOperand(self.buf[w])

    @staticmethod
    def stacked(waves, depth: int) -> "WaveOperand":
        """A copy of equally wide `waves`, in order, as one operand of
        `depth` waves; the waves past the last are empty (no lane
        active) and the program does not run them."""
        buf = np.zeros((depth,) + waves[0].buf.shape, dtype=np.int64)
        np.stack([w.buf for w in waves], out=buf[: len(waves)])
        return WaveOperand(buf)

    def narrowed(self, lanes: int) -> "WaveOperand":
        """The first `lanes` lanes as an operand of its own."""
        return WaveOperand(np.ascontiguousarray(self.buf[..., :lanes]))

    def asks(self, behavior: int) -> bool:
        """Whether any lane carries one of the `behavior` bits (read
        off the shared word: no field view is built)."""
        return bool((self.buf[..., OP_GROUP_BEHAVIOR, :] & (behavior << 32)).any())

    def stamp(self, now: int) -> "WaveOperand":
        self.buf[..., OP_NOW, 0] = now
        return self


def unpack_operand(operand):
    """(RequestBatch, home, now) from one uploaded operand, inside the
    jit: THE unpack every layout, the paged kernels, the mesh and the
    replica tier share. `operand` is (OPERAND_ROWS, B) int64."""
    gb = operand[OP_GROUP_BEHAVIOR]
    aa = operand[OP_ALGO_ACTIVE]
    batch = RequestBatch(
        group=gb.astype(jnp.int32),  # the low half (the cast wraps)
        behavior=(gb >> 32).astype(jnp.int32),
        algo=aa.astype(jnp.int8),
        active=((aa >> 8) & 0xFF) != 0,
        **{f: operand[i] for i, f in enumerate(_OP_I64)},
    )
    return batch, operand[OP_HOME], operand[OP_NOW, 0]


def probed_waves(probe, operand):
    """THE body of every layout's probe_exists, inside its jit:
    `probe(batch, now) -> bool[lanes]` over one wave's uploaded operand,
    (B,), or over a stacked run's (W, OPERAND_ROWS, B) as ONE batch of
    W * B lanes, (W, B): the table is only read, so the waves are
    independent, and a flush stamps one `now` into all of them."""
    if operand.ndim == 2:
        batch, _home, now = unpack_operand(operand)
        return probe(batch, now)
    depth, rows, lanes = operand.shape
    batch, _home, now = unpack_operand(
        jnp.swapaxes(operand, 0, 1).reshape(rows, depth * lanes)
    )
    return probe(batch, now).reshape(depth, lanes)


@jax.jit
def operand_waves(operand):
    """The waves of a stacked operand that is on the device, as
    operands of their own and placed as it is: one small program and no
    upload, for the run whose stacked probe found a lane not live
    (runtime/engine.py _execute_waves)."""
    return tuple(operand[w] for w in range(operand.shape[0]))


def vary_like(values, refs):
    """`values` with every leaf widened to vary over the mesh axes that
    any leaf of `refs` varies over. Inside a shard_map the carry of a
    loop and the two branches of a conditional must agree on those
    axes; outside one there are none and nothing is done."""
    axes = frozenset().union(
        *(jax.typeof(x).vma for x in jax.tree.leaves(refs))
    )
    return jax.tree.map(
        lambda x: jax.lax.pcast(
            x, tuple(axes - jax.typeof(x).vma), to="varying"
        ),
        values,
    )


def packed_waves(step, state, operand, with_store: bool):
    """THE body of every packed launch (each layout, the paged kernels,
    the mesh): `step(state, batch, now) -> (state, DecideOutput)` applied
    to one uploaded operand, the output packed to one int64 array.

    A (OPERAND_ROWS, B) operand is one wave and gives one vector. A
    (W, OPERAND_ROWS, B) operand is a run of waves of one flush: they
    are applied in order, each to the state the one before left, inside
    this one program, and the output is (W, rows * B + 4), one vector a
    wave. The loop turns as often as the operand holds waves up to its
    last one with an active lane, which the program reads from its
    input: the empty waves that pad a run to a compiled depth cost no
    device time and leave zeros in their rows."""
    if operand.ndim == 2:
        batch, _home, now = unpack_operand(operand)
        state, out = step(state, batch, now)
        return state, pack_output(out, with_store)
    depth, _, lanes = operand.shape
    active = ((operand[:, OP_ALGO_ACTIVE, :] >> 8) & 0xFF) != 0
    real = jnp.max(
        jnp.where(
            active.any(axis=1), jnp.arange(1, depth + 1, dtype=jnp.int32), 0
        )
    )
    rows = OUT_STORE_ROWS if with_store else OUT_LANE_ROWS
    outs = vary_like(
        jnp.zeros((depth, rows * lanes + OUT_TOTALS), dtype=jnp.int64), state
    )

    def wave(w, carry):
        state, outs = carry
        batch, _home, now = unpack_operand(operand[w])
        state, out = step(state, batch, now)
        return state, outs.at[w].set(pack_output(out, with_store))

    return jax.lax.fori_loop(jnp.int32(0), real, wave, (state, outs))


def pack_output(out: "DecideOutput", with_store: bool):
    """One int64 vector from a DecideOutput, inside the jit: the four
    answer rows (and slot, evicted_hi/lo, freed when `with_store`), then
    the four totals."""
    rows = [out.status, out.limit, out.remaining, out.reset_time]
    if with_store:
        rows += [out.slot, out.evicted_hi, out.evicted_lo, out.freed]
    lanes = jnp.stack([r.astype(jnp.int64) for r in rows]).reshape(-1)
    totals = jnp.stack(
        [out.hits, out.misses, out.unexpired_evictions, out.over_limit]
    ).astype(jnp.int64)
    return jnp.concatenate([lanes, totals])


def split_output(vec: np.ndarray, with_store: bool = False):
    """(rows (R, B), totals (4,)) views of one wave's output vector on
    the host; rows index by OUT_*."""
    r = OUT_STORE_ROWS if with_store else OUT_LANE_ROWS
    return vec[:-OUT_TOTALS].reshape(r, -1), vec[-OUT_TOTALS:]


def output_struct(vec, with_store: bool = False) -> "DecideOutput":
    """A DecideOutput of host arrays from one output vector (tests and
    tools; fields a store-less vector lacks are None)."""
    rows, tot = split_output(np.asarray(vec), with_store)  # guberlint: allow-host-sync -- tests/tools helper, never on the serving path (the engine reads in _read_waves)
    extra = (
        (rows[OUT_SLOT], rows[OUT_EVICTED_HI], rows[OUT_EVICTED_LO],
         rows[OUT_FREED] != 0)
        if with_store else (None,) * 4
    )
    return DecideOutput(
        rows[OUT_STATUS].astype(np.int8), rows[OUT_LIMIT],
        rows[OUT_REMAINING], rows[OUT_RESET_TIME], *extra, *tot,
    )


class DecideOutput(NamedTuple):
    """Per-lane decisions plus batch metrics."""

    status: jnp.ndarray  # (B,) int8
    limit: jnp.ndarray  # (B,) int64
    remaining: jnp.ndarray  # (B,) int64
    reset_time: jnp.ndarray  # (B,) int64
    slot: jnp.ndarray  # (B,) int64 slot each lane touched (N for padding)
    # Displaced occupant's key when this lane's insert evicted a DIFFERENT
    # key from the slot ((0,0) = none). The engine's store path tracks
    # these as flush events: a key whose last event is a displacement is
    # dropped from the host key dictionary so its next request prefetches
    # the persisted counter outside the device lock (the reference
    # re-consults the store on every cache miss, algorithms.go:45-51).
    evicted_hi: jnp.ndarray  # (B,) int64
    evicted_lo: jnp.ndarray  # (B,) int64
    # Slot freed by token-bucket RESET_REMAINING (the only path where the
    # reference removes the persisted entry, algorithms.go:78-90).
    freed: jnp.ndarray  # (B,) bool
    # metrics (scalars): cache hits, misses, unexpired evictions, over-limit
    hits: jnp.ndarray
    misses: jnp.ndarray
    unexpired_evictions: jnp.ndarray
    over_limit: jnp.ndarray


def _step_operand(batch, *home_now) -> WaveOperand:
    *home, now = home_now
    return WaveOperand.of(batch, int(now), home[0] if home else None)


def batch_entry(packed, with_store: bool = False):
    """A packed program under the RequestBatch signature, for tests and
    tools: entry(state, batch, now) or entry(state, batch, home, now)
    packs the host batch into one WaveOperand, launches `packed(state,
    operand)` and returns (state, DecideOutput of host arrays)."""

    def entry(state, batch, *home_now):
        state, vec = packed(state, _step_operand(batch, *home_now).buf)
        return state, output_struct(vec, with_store)

    return entry


def run_entry(packed, with_store: bool = False):
    """batch_entry's twin for a run of waves, as an engine stacks them:
    entry(state, steps, depth=None), `steps` a list of (batch, now) or
    (batch, home, now) of one width, is ONE launch of `packed` over the
    steps' operands stacked to `depth` waves (no fewer than the steps;
    the waves past the last are empty). Returns (state, one DecideOutput
    of host arrays a step, the whole (depth, L) output on the host)."""

    def entry(state, steps, depth=None):
        run = WaveOperand.stacked(
            [_step_operand(*step) for step in steps], depth or len(steps)
        )
        state, vecs = packed(state, run.buf)
        vecs = np.asarray(vecs)  # guberlint: allow-host-sync -- tests/tools helper, never on the serving path
        return (
            state,
            [output_struct(vecs[i], with_store) for i in range(len(steps))],
            vecs,
        )

    return entry
