"""The decide kernel: one vectorized step replacing the reference hot loop.

_decide_impl(table, batch, now) -> (table', DecideOutput)

This single traced function subsumes the reference's entire L3 execution
engine — WorkerPool dispatch (reference workers.go:261-324), LRU cache
get/add/evict (reference lrucache.go:88-161), and every branch of
tokenBucket/leakyBucket (reference algorithms.go:37-493) — as masked int64
vector ops over a W-way set-associative HBM slot table. It is jitted in
one place, as the body of the packed launch (ops/kernels.py
decide_packed, ops/layout.py packed_waves): the table buffers are
donated there, so the update is in-place on device.

Branch semantics are bit-for-bit identical to models/oracle.py (the spec),
which is fuzz-verified in tests/test_kernel_fuzz.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from gubernator_tpu.api.types import Algorithm, Behavior, Status
from gubernator_tpu.models.bucket import FIXED_SHIFT, MAX_ELAPSED_MS
from gubernator_tpu.ops.layout import (
    DecideOutput,
    RequestBatch,
    SlotTable,
    gathered_rows,
    packed_cols,
    probed_waves,
    vary_like,
)

I64 = jnp.int64
U64 = jnp.uint64


def _divmod_pos(num, den):
    """(num // den, num % den) for int64 `num` and int64 `den` >= 1,
    bit-for-bit what `//` and `%` give, as a shift-subtract loop.

    The TPU has no 64-bit integer divide, and XLA's own expansion of one
    compiles for seconds and grows with the batch width: the fused
    decide's fourteen `//` and `%` were ~40 s of compile at 128 lanes
    and 140-350 s at 1,024 on a v5e (jax 0.9.0, libtpu 0.0.34), every
    other part of the program together under 10 s. The loop body
    compiles once. A negative numerator goes through its complement:
    floor(a / d) == ~floor(~a / d), so the core only sees values below
    2^63 — whose top bit is 0, hence 63 steps."""
    neg = num < 0
    n = jnp.where(neg, ~num, num).astype(U64)
    d = den.astype(U64)

    def step(i, qr):
        q, r = qr
        k = (62 - i).astype(U64)
        r = (r << U64(1)) | ((n >> k) & U64(1))
        ge = r >= d
        return q | (ge.astype(U64) << k), jnp.where(ge, r - d, r)

    zero = jnp.zeros_like(n)
    q, r = jax.lax.fori_loop(0, 63, step, (zero, zero))
    q, r = q.astype(I64), r.astype(I64)
    return jnp.where(neg, ~q, q), jnp.where(neg, den - 1 - r, r)


def _leak_fixed(elapsed, limit, rate_num, burst):
    """Vectorized twin of models.bucket.leak_fixed (same int64 ops)."""
    limit_g = jnp.maximum(limit, 1)
    rn = jnp.maximum(rate_num, 1)
    cap_t = burst + 1
    e_c = jnp.clip(elapsed, 0, MAX_ELAPSED_MS)
    # the two independent divisions share one loop
    q, r = _divmod_pos(jnp.stack([e_c, cap_t]), jnp.stack([rn, limit_g]))
    a, e = q[0], r[0]
    a_lim = q[1] + 1
    a_c = jnp.minimum(a, a_lim)
    whole = a_c * limit
    saturated = (a > a_lim) | (whole >= cap_t)
    hi = limit >> 16
    lo = limit & 0xFFFF
    p1 = e * hi
    q1, r1 = _divmod_pos(p1, rn)
    q2, r2 = _divmod_pos(r1 << 16, rn)
    p2 = e * lo
    q3, r3 = _divmod_pos(r2 + p2, rn)
    tok = (q1 << 16) + q2 + q3
    frac_s, _ = _divmod_pos(r3 << FIXED_SHIFT, rn)
    cap_s = cap_t << FIXED_SHIFT
    leak = jnp.minimum(((whole + tok) << FIXED_SHIFT) + frac_s, cap_s)
    leak = jnp.where(saturated, cap_s, leak)
    return jnp.where(elapsed <= 0, jnp.zeros_like(leak), leak)


def _choose_slot(table: SlotTable, batch: RequestBatch, now, ways: int):
    """Probe each request's W-way group: find the live matching way, or the
    way to insert into (matched-expired > empty > expired > LRU)."""
    grp_base = batch.group.astype(I64) * ways
    way_ix = grp_base[:, None] + jnp.arange(ways, dtype=I64)[None, :]  # (B, W)

    w_key_hi = table.key_hi[way_ix]
    w_key_lo = table.key_lo[way_ix]
    w_used = table.used[way_ix]
    w_expire = table.expire_at[way_ix]
    w_invalid = table.invalid_at[way_ix]
    w_lru = table.lru[way_ix]

    # Lazy expiry on read (reference cache.go:43-57, lrucache.go:115-118)
    w_expired = w_used & (
        (w_expire < now) | ((w_invalid != 0) & (w_invalid < now))
    )
    w_match = (
        w_used
        & (w_key_hi == batch.key_hi[:, None])
        & (w_key_lo == batch.key_lo[:, None])
    )

    live_match = w_match & ~w_expired
    exists = jnp.any(live_match, axis=1)
    matched_way = jnp.argmax(live_match, axis=1)

    # Insertion priority: matched-but-expired way (must reuse to avoid
    # duplicate keys) > empty > any expired > least-recently-used.
    cat = jnp.where(
        w_match & w_expired,
        0,
        jnp.where(~w_used, 1, jnp.where(w_expired, 2, 3)),
    ).astype(I64)
    # Composite score: category dominates; among live ways, oldest lru wins;
    # otherwise lowest way index (deterministic).
    tie = jnp.where(cat == 3, jnp.clip(w_lru, 0, (1 << 44) - 1), way_ix - grp_base[:, None])
    score = (cat << 44) + tie
    insert_way = jnp.argmin(score, axis=1)

    way = jnp.where(exists, matched_way, insert_way)
    slot = grp_base + way
    pick = jax.vmap(lambda r, w: r[w])  # row-wise way selection
    # Eviction metric: inserting over a live (used, unexpired) slot
    sel = pick(cat, insert_way)
    evicts_live = (~exists) & (sel == 3) & batch.active
    # Displaced occupant key, recovered from the ALREADY-GATHERED way
    # arrays (re-gathering from the table costs ~1.7x kernel throughput
    # on CPU): the chosen way's current occupant, when it holds a
    # DIFFERENT live key than the request.
    old_hi = pick(w_key_hi, way)
    old_lo = pick(w_key_lo, way)
    old_used = pick(w_used, way)
    displaced = (
        batch.active
        & ~exists
        & old_used
        & ((old_hi != batch.key_hi) | (old_lo != batch.key_lo))
    )
    evicted_hi = jnp.where(displaced, old_hi, 0)
    evicted_lo = jnp.where(displaced, old_lo, 0)
    return slot, exists, evicts_live, evicted_hi, evicted_lo


def _token_paths(batch: RequestBatch, st, b_greg, b_reset, b_drain, exists_any, now):
    """All token-bucket branches (reference algorithms.go:37-257) as masks.

    Returns (state_update, resp) where state fields are full-lane values to
    scatter for lanes whose algo==TOKEN_BUCKET.
    """
    r_hits, r_limit = batch.hits, batch.limit
    created = batch.created_at

    # --- existing-item path (state algo == TOKEN and live) ---
    # limit hot-change (algorithms.go:105-113)
    limit_changed = st["limit"] != r_limit
    rem0 = jnp.where(
        limit_changed,
        jnp.maximum(st["remaining"] + (r_limit - st["limit"]), 0),
        st["remaining"],
    )
    # duration hot-change, possibly renewing (algorithms.go:122-147)
    dur_changed = st["duration"] != batch.duration
    expire1 = jnp.where(b_greg, batch.greg_expire, st["stamp"] + batch.duration)
    renew = dur_changed & (expire1 <= created)
    expire2 = jnp.where(renew, created + batch.duration, expire1)
    stamp1 = jnp.where(renew, created, st["stamp"])
    rem1 = jnp.where(renew, r_limit, rem0)
    new_expire = jnp.where(dur_changed, expire2, st["expire_at"])
    rl_reset = jnp.where(dur_changed, expire2, st["expire_at"])

    # branch masks in reference order (hits==0 -> at-limit -> exact -> over)
    m_hits0 = r_hits == 0
    m_atlim = ~m_hits0 & (rem0 == 0) & (r_hits > 0)  # STALE pre-renewal rem
    m_exact = ~m_hits0 & ~m_atlim & (rem1 == r_hits)
    m_over = ~m_hits0 & ~m_atlim & ~m_exact & (r_hits > rem1)
    m_cons = ~m_hits0 & ~m_atlim & ~m_exact & ~m_over

    rem_state = jnp.where(
        m_exact,
        0,
        jnp.where(
            m_over,
            jnp.where(b_drain, 0, rem1),
            jnp.where(m_cons, rem1 - r_hits, rem1),
        ),
    )
    sticky = st["status"].astype(jnp.int8)
    status_state = jnp.where(m_atlim, jnp.int8(Status.OVER_LIMIT), sticky)
    resp_status = jnp.where(
        m_atlim | m_over, jnp.int8(Status.OVER_LIMIT), sticky
    )
    resp_rem = jnp.where(
        m_exact,
        0,
        jnp.where(
            m_over,
            jnp.where(b_drain, 0, rem0),
            jnp.where(m_cons, rem1 - r_hits, rem0),
        ),
    )

    # --- new-item path (algorithms.go:206-257) ---
    expire_new = jnp.where(b_greg, batch.greg_expire, created + batch.duration)
    over_new = r_hits > r_limit
    rem_new = jnp.where(over_new, r_limit, r_limit - r_hits)
    resp_status_new = jnp.where(
        over_new, jnp.int8(Status.OVER_LIMIT), jnp.int8(Status.UNDER_LIMIT)
    )

    # --- RESET_REMAINING on an existing item (algorithms.go:78-90): free
    # the slot, fixed response. Applies whatever the stored algorithm is.
    m_reset = exists_any & b_reset

    fresh = ~exists_any | (st["algo"] != jnp.int8(Algorithm.TOKEN_BUCKET))
    use_new = ~m_reset & fresh

    state = dict(
        used=~m_reset,
        limit=r_limit,
        duration=batch.duration,
        remaining=jnp.where(use_new, rem_new, rem_state),
        stamp=jnp.where(use_new, created, stamp1),
        expire_at=jnp.where(use_new, expire_new, new_expire),
        status=jnp.where(
            use_new, jnp.int8(Status.UNDER_LIMIT), status_state
        ),
        burst=jnp.zeros_like(r_limit),
    )
    resp = dict(
        status=jnp.where(
            m_reset,
            jnp.int8(Status.UNDER_LIMIT),
            jnp.where(use_new, resp_status_new, resp_status),
        ),
        remaining=jnp.where(
            m_reset,
            r_limit,
            jnp.where(
                use_new, jnp.where(over_new, r_limit, r_limit - r_hits), resp_rem
            ),
        ),
        reset_time=jnp.where(
            m_reset, 0, jnp.where(use_new, expire_new, rl_reset)
        ),
        over=~m_reset & jnp.where(use_new, over_new, m_atlim | m_over),
    )
    return state, resp


def _leaky_paths(batch: RequestBatch, st, b_greg, b_reset, b_drain, exists_any, now):
    """All leaky-bucket branches (reference algorithms.go:260-493)."""
    r_hits, r_limit, r_burst = batch.hits, batch.limit, batch.burst
    created = batch.created_at
    S = FIXED_SHIFT

    # --- existing-item path ---
    rem_s0 = jnp.where(b_reset, r_burst << S, st["remaining"])
    burst_changed = st["burst"] != r_burst
    rem_s1 = jnp.where(
        burst_changed & (r_burst > (rem_s0 >> S)), r_burst << S, rem_s0
    )
    # expiry refresh when hits != 0 (algorithms.go:356-358)
    expire_upd = jnp.where(
        r_hits != 0, created + batch.eff_duration, st["expire_at"]
    )
    # leak accrual (algorithms.go:360-367); burst already updated to r_burst
    elapsed = created - st["stamp"]
    leak_s = _leak_fixed(elapsed, r_limit, batch.rate_num, r_burst)
    leaked = (leak_s >> S) > 0
    rem_s2 = jnp.where(leaked, rem_s1 + leak_s, rem_s1)
    stamp1 = jnp.where(leaked, created, st["stamp"])
    # unconditional burst clamp (algorithms.go:369-371)
    rem_s3 = jnp.where((rem_s2 >> S) > r_burst, r_burst << S, rem_s2)

    # int64(rate) for the existing- and the new-item path (which takes
    # it from the RAW duration field), in one division loop
    ri_both, _ = _divmod_pos(
        jnp.stack([batch.rate_num, batch.duration]),
        jnp.maximum(r_limit, 1)[None, :],
    )
    ri, ri_new = ri_both[0], ri_both[1]
    rem_int = rem_s3 >> S

    # branch masks in reference order (at-limit -> exact -> over -> hits==0)
    m_atlim = (rem_int == 0) & (r_hits > 0)
    m_exact = ~m_atlim & (rem_int == r_hits)
    m_over = ~m_atlim & ~m_exact & (r_hits > rem_int)
    m_hits0 = ~m_atlim & ~m_exact & ~m_over & (r_hits == 0)
    m_cons = ~m_atlim & ~m_exact & ~m_over & ~m_hits0

    rem_s_final = jnp.where(
        m_exact,
        0,
        jnp.where(
            m_over,
            jnp.where(b_drain, 0, rem_s3),
            jnp.where(m_cons, rem_s3 - (r_hits << S), rem_s3),
        ),
    )
    resp_rem = jnp.where(
        m_exact,
        0,
        jnp.where(
            m_over,
            jnp.where(b_drain, 0, rem_int),
            jnp.where(m_cons, rem_s_final >> S, rem_int),
        ),
    )
    resp_status = jnp.where(
        m_atlim | m_over, jnp.int8(Status.OVER_LIMIT), jnp.int8(Status.UNDER_LIMIT)
    )
    base_reset = created + (r_limit - rem_int) * ri
    resp_reset = jnp.where(
        m_exact,
        created + r_limit * ri,
        jnp.where(m_cons, created + (r_limit - (rem_s_final >> S)) * ri, base_reset),
    )

    # --- new-item path (algorithms.go:437-493); rate from the RAW duration
    # field (pre-Gregorian-override quirk) ---
    over_new = r_hits > r_burst
    rem_new = r_burst - r_hits
    rem_s_new = jnp.where(over_new, 0, rem_new << S)
    resp_rem_new = jnp.where(over_new, 0, rem_new)
    resp_reset_new = created + (r_limit - resp_rem_new) * ri_new
    expire_new = created + batch.eff_duration

    fresh = ~exists_any | (st["algo"] != jnp.int8(Algorithm.LEAKY_BUCKET))
    use_new = fresh

    state = dict(
        used=jnp.ones_like(fresh),
        limit=r_limit,
        # Found path stores the RAW duration (algorithms.go:333); new items
        # store the effective duration (algorithms.go:455-456).
        duration=jnp.where(use_new, batch.eff_duration, batch.duration),
        remaining=jnp.where(use_new, rem_s_new, rem_s_final),
        stamp=jnp.where(use_new, created, stamp1),
        expire_at=jnp.where(use_new, expire_new, expire_upd),
        status=jnp.zeros_like(st["status"]),  # leaky has no stored status
        burst=r_burst,
    )
    resp = dict(
        status=jnp.where(
            use_new,
            jnp.where(over_new, jnp.int8(Status.OVER_LIMIT), jnp.int8(Status.UNDER_LIMIT)),
            resp_status,
        ),
        remaining=jnp.where(use_new, resp_rem_new, resp_rem),
        reset_time=jnp.where(use_new, resp_reset_new, resp_reset),
        over=jnp.where(use_new, over_new, m_atlim | m_over),
    )
    return state, resp


def _both_paths(batch: RequestBatch, st, b_greg, b_reset, b_drain, exists_any, now):
    """(state_update, resp) of every lane by its own algorithm: THE
    selection both layouts share. The token path is lane arithmetic and
    runs for every wave; the leaky path holds all six division loops
    (five in _leak_fixed, one for the rate) and runs only where the wave
    carries a leaky lane. A wave without one hands the token values to
    both sides of the per-lane select, which takes the token side in
    every lane either way, so the result is the same bit for bit. The
    conditional wraps lane vectors only, never the table."""
    args = (batch, st, b_greg, b_reset, b_drain, exists_any, now)
    tok = _token_paths(*args)
    is_leaky = batch.algo == jnp.int8(Algorithm.LEAKY_BUCKET)
    # (inside a shard_map both branches vary over the inputs' mesh axes)
    lky = jax.lax.cond(
        jnp.any(is_leaky),
        lambda: vary_like(_leaky_paths(*args), args),
        lambda: vary_like(tok, args),
    )
    return jax.tree.map(lambda t, l: jnp.where(is_leaky, l, t), tok, lky)


def _decide_impl(table: SlotTable, batch: RequestBatch, now, *, ways: int):
    now = jnp.asarray(now, dtype=I64)
    slot, exists, evicts_live, evicted_hi, evicted_lo = _choose_slot(
        table, batch, now, ways
    )

    # Gather the chosen slot's state (garbage for fresh lanes; masked off).
    st = dict(
        algo=table.algo[slot],
        status=table.status[slot],
        limit=table.limit[slot],
        duration=table.duration[slot],
        remaining=table.remaining[slot],
        stamp=table.stamp[slot],
        expire_at=table.expire_at[slot],
        burst=table.burst[slot],
        invalid_at=table.invalid_at[slot],
    )
    # Fresh lanes must not see stale values in arithmetic that could
    # overflow; zero them out (semantically they're ignored anyway).
    for k in st:
        if k in ("algo", "status"):
            st[k] = jnp.where(exists, st[k], jnp.zeros_like(st[k]))
        else:
            st[k] = jnp.where(exists, st[k], jnp.zeros_like(st[k]))

    bhv = batch.behavior
    b_greg = (bhv & int(Behavior.DURATION_IS_GREGORIAN)) != 0
    b_reset = (bhv & int(Behavior.RESET_REMAINING)) != 0
    b_drain = (bhv & int(Behavior.DRAIN_OVER_LIMIT)) != 0

    new_state, resp = _both_paths(batch, st, b_greg, b_reset, b_drain, exists, now)

    # Scatter back. Inactive (padding) lanes target index N -> dropped.
    n = table.num_slots
    idx = jnp.where(batch.active, slot, n)
    freed = ~new_state["used"]  # token RESET_REMAINING frees the slot

    def upd(arr, val):
        return arr.at[idx].set(val, mode="drop")

    new_table = SlotTable(
        key_hi=upd(table.key_hi, jnp.where(freed, 0, batch.key_hi)),
        key_lo=upd(table.key_lo, jnp.where(freed, 0, batch.key_lo)),
        used=upd(table.used, new_state["used"]),
        algo=upd(table.algo, batch.algo),
        status=upd(table.status, new_state["status"]),
        limit=upd(table.limit, new_state["limit"]),
        duration=upd(table.duration, new_state["duration"]),
        remaining=upd(table.remaining, new_state["remaining"]),
        stamp=upd(table.stamp, new_state["stamp"]),
        expire_at=upd(table.expire_at, new_state["expire_at"]),
        # The store's invalidation mark survives updates on a live entry
        # (reference: algorithms never touch CacheItem.InvalidAt); fresh
        # inserts and freed slots clear it.
        invalid_at=upd(
            table.invalid_at,
            jnp.where(exists & ~freed, st["invalid_at"], jnp.zeros_like(batch.key_hi)),
        ),
        burst=upd(table.burst, new_state["burst"]),
        lru=upd(table.lru, jnp.broadcast_to(now, idx.shape)),
    )

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
    return new_table, out


def _probe_exists_impl(table: SlotTable, batch, now, ways: int):
    grp_base = batch.group.astype(I64) * ways
    way_ix = grp_base[:, None] + jnp.arange(ways, dtype=I64)[None, :]
    w_used = table.used[way_ix]
    w_invalid = table.invalid_at[way_ix]
    w_expired = w_used & (
        (table.expire_at[way_ix] < now) | ((w_invalid != 0) & (w_invalid < now))
    )
    live = (
        w_used
        & ~w_expired
        & (table.key_hi[way_ix] == batch.key_hi[:, None])
        & (table.key_lo[way_ix] == batch.key_lo[:, None])
    )
    return batch.active & jnp.any(live, axis=1)


@functools.partial(jax.jit, static_argnames=("ways",))
def probe_exists(table: SlotTable, operand, ways: int = 8):
    """Ground-truth residency probe: True per lane iff the lane is active
    and its key has a LIVE entry in its group (same lazy-expiry +
    invalidation semantics as the decide kernel's match). The engine uses
    this right before each wave to drive store read-through on actual
    table misses — the reference consults the store on every cache miss
    (algorithms.go:45-51), and the table, not host bookkeeping, is what
    defines a miss. `operand` is the wave's own uploaded operand, the one
    its decide takes next (ops/layout.py unpack_operand): the probe
    brings nothing across the boundary but its answer. A stacked run's
    operand is probed whole, (W, B) (ops/layout.py probed_waves)."""
    return probed_waves(
        lambda batch, now: _probe_exists_impl(table, batch, now, ways),
        operand,
    )


def _gather_cols(table: SlotTable, safe):
    """The (NCOLS, B) packed columns of in-range slots `safe` (B,)."""
    return jnp.stack(packed_cols(jax.tree.map(lambda a: a[safe], table)))


@functools.partial(jax.jit, static_argnames=("from_output",))
def gather_rows(table: SlotTable, slots, from_output: bool = False):
    """Post-decide row readback for the Store write-behind seam: each
    slot's full state as one packed (NCOLS, B) int64 array (padding
    slots index N -> zeros); ops/layout.py gathered_rows / wide_rows."""
    return gathered_rows(
        functools.partial(_gather_cols, table),
        slots, table.num_slots, from_output,
    )
