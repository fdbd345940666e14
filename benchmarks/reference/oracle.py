"""The benchmark's plain reference: token- and leaky-bucket semantics.

A dict-backed, sequential, pure-Python limiter: the same requests in the
same order give the answers the served path must give, bit for bit. Taken
from ``gubernator_tpu/models/oracle.py`` and ``models/bucket.py`` as they
stood at PR 21 and from then on the benchmark's own yardstick: it imports
nothing of the program and shares no code with ``ops/``. Left out, because
no traffic file can ask for them: the Store plugin and
DURATION_IS_GREGORIAN (a request carrying that flag is an error here).
It has no capacity: eviction is the table's business, see check.py.

Branch order follows upstream (mailgun/gubernator algorithms.go:37-493),
quirks included: the token bucket's sticky status, over-limit requests
that do not consume, the stale response when a duration change renews an
expired item.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

TOKEN_BUCKET, LEAKY_BUCKET = 0, 1
UNDER_LIMIT, OVER_LIMIT = 0, 1
GLOBAL = 2
DURATION_IS_GREGORIAN = 4
RESET_REMAINING = 8
DRAIN_OVER_LIMIT = 32
MAX_BATCH_SIZE = 1000

# Leaky buckets keep their fractional remaining in Q44.20 fixed point.
FIXED_SHIFT = 20
MAX_ELAPSED_MS = 1 << 42


@dataclass
class Request:
    name: str = ""
    unique_key: str = ""
    hits: int = 0
    limit: int = 0
    duration: int = 0  # milliseconds
    algorithm: int = TOKEN_BUCKET
    behavior: int = 0
    burst: int = 0
    created_at: Optional[int] = None  # epoch ms; the server's clock if None

    def hash_key(self) -> str:
        return self.name + "_" + self.unique_key


@dataclass
class Response:
    status: int = UNDER_LIMIT
    limit: int = 0
    remaining: int = 0
    reset_time: int = 0
    error: str = ""

    def as_tuple(self) -> tuple:
        return (self.status, self.limit, self.remaining, self.reset_time,
                self.error)


@dataclass
class TokenBucketState:
    status: int = UNDER_LIMIT
    limit: int = 0
    duration: int = 0
    remaining: int = 0
    created_at: int = 0


@dataclass
class LeakyBucketState:
    limit: int = 0
    duration: int = 0
    remaining_s: int = 0  # Q44.20
    updated_at: int = 0
    burst: int = 0


def validate(r: Request) -> Optional[str]:
    if not r.unique_key:
        return "field 'unique_key' cannot be empty"
    if not r.name:
        return "field 'namespace' cannot be empty"
    return None

def leak_fixed(elapsed: int, limit: int, rate_num: int, burst: int) -> int:
    """Fixed-point leak accrual: min(floor(elapsed*limit*2^20 / rate_num),
    (burst+1) << 20), for elapsed >= 0.

    The reference computes `leak = float64(elapsed) / rate` with
    `rate = rate_num / limit` (reference algorithms.go:336, 360-362). The
    result is saturated just above `burst` because the caller clamps
    remaining to burst immediately after accrual (algorithms.go:369-371),
    so any leak >= burst+1 tokens is observationally equivalent.

    Every intermediate fits int64 when elapsed <= 2^42, rate_num <= 2^42,
    limit <= 2^31, burst <= 2^31 — the same ops run under jit in the
    device kernel. Division is by-parts (16-bit split of `limit`) to avoid
    the 128-bit product elapsed*limit*2^20.
    """
    if elapsed <= 0:
        return 0
    limit_g = max(limit, 1)
    rate_num = max(rate_num, 1)  # duration 0 => immediate full refill
    cap_t = burst + 1

    e_c = min(elapsed, MAX_ELAPSED_MS)
    a = e_c // rate_num  # whole rate-periods elapsed
    e = e_c % rate_num  # partial period, < rate_num

    # Whole-period token credit a*limit, saturated at cap_t.
    a_lim = cap_t // limit_g + 1
    a_c = min(a, a_lim)
    whole = a_c * limit  # <= cap_t + 2*limit, fits easily
    saturated = (a > a_lim) | (whole >= cap_t)

    # Partial-period credit: floor(e*limit / rate_num) tokens + fixed frac.
    hi = limit >> 16
    lo = limit & 0xFFFF
    p1 = e * hi
    q1, r1 = divmod(p1, rate_num)
    q2, r2 = divmod(r1 << 16, rate_num)
    p2 = e * lo
    q3, r3 = divmod(r2 + p2, rate_num)
    tok = (q1 << 16) + q2 + q3  # == e*limit // rate_num exactly
    frac_s = (r3 << FIXED_SHIFT) // rate_num

    cap_s = cap_t << FIXED_SHIFT
    if saturated:
        return cap_s
    leak_s = ((whole + tok) << FIXED_SHIFT) + frac_s
    return min(leak_s, cap_s)


def rate_int(rate_num: int, limit: int) -> int:
    """int64(rate) where rate = rate_num/limit (reference
    algorithms.go:336, 377). Guarded against limit==0 (the reference
    produces +Inf there; tests never exercise it)."""
    return rate_num // max(limit, 1)


def _i64(x: int) -> int:
    """Wrap to int64 like Go's arithmetic (and the kernel's): the spec is
    bug-for-bug at adversarial extremes where products overflow."""
    x &= (1 << 64) - 1
    return x - (1 << 64) if x >= (1 << 63) else x


@dataclass
class CacheEntry:
    """Host-side mirror of the reference CacheItem (reference cache.go:29-41)."""

    algorithm: int
    key: str
    value: object
    expire_at: int = 0
    invalid_at: int = 0

    def is_expired(self, now: int) -> bool:
        # reference cache.go:43-57
        if self.invalid_at != 0 and self.invalid_at < now:
            return True
        return self.expire_at < now


class Reference:
    """Sequential in-memory rate limiter with exact reference semantics."""

    def __init__(self):
        self.cache: Dict[str, CacheEntry] = {}

    # -- public API ---------------------------------------------------------

    def get_rate_limits(
        self, reqs: List[Request], now_ms: int, is_owner: bool = True
    ) -> List[Response]:
        if len(reqs) > MAX_BATCH_SIZE:
            raise ValueError(
                f"Requests.RateLimits list too large; max size is '{MAX_BATCH_SIZE}'"
            )
        out = []
        for r in reqs:
            err = validate(r)
            if err is None and r.behavior & DURATION_IS_GREGORIAN:
                err = "DURATION_IS_GREGORIAN is outside the reference"
            if err is not None:
                out.append(Response(error=err))
                continue
            out.append(self.decide(r, now_ms, is_owner))
        return out

    def decide(
        self, r: Request, now_ms: int, is_owner: bool = True
    ) -> Response:
        if r.created_at is None:
            r.created_at = now_ms
        if r.algorithm == LEAKY_BUCKET:
            return self._leaky_bucket(r, now_ms, is_owner)
        return self._token_bucket(r, now_ms, is_owner)

    def export(self) -> List[dict]:
        """Every bucket held, in upstream's CacheItem terms (reference
        store.go:29-43): what a Loader is handed at shutdown and hands back
        at start. Token buckets only: a leaky bucket's remainder is a
        fixed-point form of this port, not upstream's field."""
        rows = []
        for item in self.cache.values():
            if item.algorithm != TOKEN_BUCKET:
                raise ValueError(f"{item.key}: only token buckets are exported")
            t: TokenBucketState = item.value
            rows.append({
                "key": item.key, "algorithm": item.algorithm,
                "expire_at": item.expire_at, "status": t.status,
                "limit": t.limit, "duration": t.duration,
                "remaining": t.remaining, "created_at": t.created_at,
            })
        return rows

    # -- cache access with lazy expiry --------------------------------------

    def _get(self, r: Request, now_ms: int) -> Optional[CacheEntry]:
        key = r.hash_key()
        item = self.cache.get(key)
        if item is not None and item.is_expired(now_ms):
            # lazy removal on read (reference lrucache.go:111-128)
            del self.cache[key]
            item = None
        return item

    def _remove(self, key: str) -> None:
        self.cache.pop(key, None)

    def _on_change(self, r: Request, item: CacheEntry, is_owner: bool) -> None:
        pass  # upstream's write-behind Store hook; no traffic attaches one

    # -- token bucket (reference algorithms.go:37-257) -----------------------

    def _token_bucket(
        self, r: Request, now_ms: int, is_owner: bool
    ) -> Response:
        key = r.hash_key()
        item = self._get(r, now_ms)

        if item is not None:
            if bool(r.behavior & RESET_REMAINING):
                # reference algorithms.go:78-90
                self._remove(key)
                return Response(
                    status=UNDER_LIMIT,
                    limit=r.limit,
                    remaining=r.limit,
                    reset_time=0,
                )
            if item.algorithm != TOKEN_BUCKET:
                # algorithm switch resets state (reference algorithms.go:91-103)
                self._remove(key)
                return self._token_bucket_new_item(r, now_ms, is_owner)

            t: TokenBucketState = item.value

            # Limit hot-change: credit/debit the difference
            # (reference algorithms.go:105-113).
            if t.limit != r.limit:
                t.remaining += r.limit - t.limit
                if t.remaining < 0:
                    t.remaining = 0
                t.limit = r.limit

            rl = Response(
                status=t.status,
                limit=r.limit,
                remaining=t.remaining,
                reset_time=item.expire_at,
            )

            # Duration hot-change, possibly renewing an expired-by-new-rules
            # item (reference algorithms.go:122-147). Note the reference does
            # NOT refresh rl.remaining after a renewal — preserved here.
            if t.duration != r.duration:
                expire = t.created_at + r.duration
                created_at = r.created_at
                if expire <= created_at:
                    expire = created_at + r.duration
                    t.created_at = created_at
                    t.remaining = t.limit
                item.expire_at = expire
                t.duration = r.duration
                rl.reset_time = expire

            self._on_change(r, item, is_owner)

            # Status/config read only (reference algorithms.go:157-159).
            if r.hits == 0:
                return rl

            # Already at the limit (reference algorithms.go:162-170).
            # Sticky: stored status flips to OVER_LIMIT.
            if rl.remaining == 0 and r.hits > 0:
                rl.status = OVER_LIMIT
                t.status = OVER_LIMIT
                return rl

            # Exact drain (reference algorithms.go:173-178).
            if t.remaining == r.hits:
                t.remaining = 0
                rl.remaining = 0
                return rl

            # Over the limit: reject WITHOUT consuming, unless
            # DRAIN_OVER_LIMIT (reference algorithms.go:182-194).
            if r.hits > t.remaining:
                rl.status = OVER_LIMIT
                if bool(r.behavior & DRAIN_OVER_LIMIT):
                    t.remaining = 0
                    rl.remaining = 0
                return rl

            t.remaining -= r.hits
            rl.remaining = t.remaining
            return rl

        return self._token_bucket_new_item(r, now_ms, is_owner)

    def _token_bucket_new_item(
        self, r: Request, now_ms: int, is_owner: bool
    ) -> Response:
        # reference algorithms.go:206-257
        created_at = r.created_at
        expire = created_at + r.duration
        t = TokenBucketState(
            status=UNDER_LIMIT,
            limit=r.limit,
            duration=r.duration,
            remaining=r.limit - r.hits,
            created_at=created_at,
        )

        rl = Response(
            status=UNDER_LIMIT,
            limit=r.limit,
            remaining=t.remaining,
            reset_time=expire,
        )

        # First request already over the limit: do not consume; note the
        # stored status stays UNDER_LIMIT (reference algorithms.go:240-248).
        if r.hits > r.limit:
            rl.status = OVER_LIMIT
            rl.remaining = r.limit
            t.remaining = r.limit

        item = CacheEntry(
            algorithm=TOKEN_BUCKET, key=r.hash_key(), value=t, expire_at=expire
        )
        self.cache[item.key] = item
        self._on_change(r, item, is_owner)
        return rl

    # -- leaky bucket (reference algorithms.go:260-493) -----------------------

    def _leaky_bucket(
        self, r: Request, now_ms: int, is_owner: bool
    ) -> Response:
        if r.burst == 0:
            r.burst = r.limit  # reference algorithms.go:264-266
        created_at = r.created_at
        key = r.hash_key()
        item = self._get(r, now_ms)

        if item is not None:
            if item.algorithm != LEAKY_BUCKET:
                # reference algorithms.go:308-318
                self._remove(key)
                return self._leaky_bucket_new_item(r, now_ms, is_owner)

            b: LeakyBucketState = item.value

            if bool(r.behavior & RESET_REMAINING):
                b.remaining_s = r.burst << FIXED_SHIFT  # algorithms.go:320-322

            # Burst hot-change (reference algorithms.go:325-330).
            if b.burst != r.burst:
                if r.burst > (b.remaining_s >> FIXED_SHIFT):
                    b.remaining_s = r.burst << FIXED_SHIFT
                b.burst = r.burst

            b.limit = r.limit
            b.duration = r.duration  # algorithms.go:332-333

            duration = r.duration
            rate_num = duration  # rate = rate_num / limit

            if r.hits != 0:
                item.expire_at = created_at + duration  # algorithms.go:356-358

            # Leak accrual since last update (algorithms.go:360-367).
            elapsed = created_at - b.updated_at
            leak_s = leak_fixed(elapsed, r.limit, rate_num, b.burst)
            if (leak_s >> FIXED_SHIFT) > 0:
                b.remaining_s += leak_s
                b.updated_at = created_at

            # Burst clamp (algorithms.go:369-371) — unconditional.
            if (b.remaining_s >> FIXED_SHIFT) > b.burst:
                b.remaining_s = b.burst << FIXED_SHIFT

            ri = rate_int(rate_num, r.limit)
            rem = b.remaining_s >> FIXED_SHIFT
            rl = Response(
                status=UNDER_LIMIT,
                limit=b.limit,
                remaining=rem,
                reset_time=_i64(created_at + (b.limit - rem) * ri),
            )

            self._on_change(r, item, is_owner)

            # Already at the limit (algorithms.go:389-395).
            if rem == 0 and r.hits > 0:
                rl.status = OVER_LIMIT
                return rl

            # Exact drain — note this precedes the hits==0 check, so a
            # status read with zero remaining truncates the stored fraction
            # (algorithms.go:398-403).
            if rem == r.hits:
                b.remaining_s = 0
                rl.remaining = 0
                rl.reset_time = _i64(created_at + (rl.limit - 0) * ri)
                return rl

            # Over the limit: no consumption unless DRAIN_OVER_LIMIT
            # (algorithms.go:407-420).
            if r.hits > rem:
                rl.status = OVER_LIMIT
                if bool(r.behavior & DRAIN_OVER_LIMIT):
                    b.remaining_s = 0
                    rl.remaining = 0
                return rl

            # Status read (algorithms.go:423-425).
            if r.hits == 0:
                return rl

            b.remaining_s -= r.hits << FIXED_SHIFT
            rl.remaining = b.remaining_s >> FIXED_SHIFT
            rl.reset_time = _i64(created_at + (rl.limit - rl.remaining) * ri)
            return rl

        return self._leaky_bucket_new_item(r, now_ms, is_owner)

    def _leaky_bucket_new_item(
        self, r: Request, now_ms: int, is_owner: bool
    ) -> Response:
        # reference algorithms.go:437-493. NOTE: the reference computes
        # `rate` from the raw duration field BEFORE the Gregorian override,
        # so under DURATION_IS_GREGORIAN the new-item rate is effectively 0
        # (duration holds the interval enum 0..5) — preserved bug-for-bug.
        created_at = r.created_at
        duration = r.duration
        ri = rate_int(duration, r.limit)

        b = LeakyBucketState(
            limit=r.limit,
            duration=duration,
            remaining_s=(r.burst - r.hits) << FIXED_SHIFT,
            updated_at=created_at,
            burst=r.burst,
        )
        rl = Response(
            status=UNDER_LIMIT,
            limit=b.limit,
            remaining=r.burst - r.hits,
            reset_time=_i64(created_at + (b.limit - (r.burst - r.hits)) * ri),
        )

        # First request over the burst (reference algorithms.go:469-477).
        if r.hits > r.burst:
            rl.status = OVER_LIMIT
            rl.remaining = 0
            rl.reset_time = _i64(created_at + (rl.limit - 0) * ri)
            b.remaining_s = 0

        item = CacheEntry(
            algorithm=LEAKY_BUCKET,
            key=r.hash_key(),
            value=b,
            expire_at=created_at + duration,
        )
        self.cache[item.key] = item
        self._on_change(r, item, is_owner)
        return rl
