"""Kernel-vs-oracle equivalence: golden sequences + randomized fuzz.

The vectorized decide kernel must reproduce the oracle's (and hence the
reference's) observable behavior bit-for-bit: status, remaining, and
reset_time for every request sequence (SURVEY.md §7 kernel branch matrix).
"""

import random

import numpy as np
import pytest

from gubernator_tpu.api.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    Status,
    SECOND,
)
from gubernator_tpu.models.oracle import OracleEngine
from gubernator_tpu.ops.encode import encode_batch
from gubernator_tpu.ops.kernels import get_kernels
from gubernator_tpu.utils.gregorian import GREGORIAN_MINUTES

NOW = 1_753_700_000_000
NUM_GROUPS = 512
WAYS = 8

# Every golden/fuzz case runs against ALL table layouts (the
# ops/kernels.py registry); they must be bit-exact twins of the oracle.
from gubernator_tpu.ops.kernels import LAYOUTS  # noqa: E402

LAYOUTS = list(LAYOUTS)


class KernelHarness:
    """Single-request-per-call harness around the jitted kernel."""

    def __init__(self, num_groups=NUM_GROUPS, ways=WAYS, batch=1, layout="wide"):
        self.K = get_kernels(layout)
        self.table = self.K.create(num_groups, ways)
        self.num_groups = num_groups
        self.ways = ways
        self.batch = batch

    def decide_one(self, r: RateLimitReq, now_ms: int):
        import copy

        rc = copy.replace(r) if hasattr(copy, "replace") else r
        b = encode_batch([rc], now_ms, self.num_groups, self.batch)
        self.table, out = self.K.decide(self.table, b, now_ms, self.ways, False)
        return (
            int(out.status[0]),
            int(out.limit[0]),
            int(out.remaining[0]),
            int(out.reset_time[0]),
        )


def check_seq(seq, num_groups=NUM_GROUPS, layout="wide"):
    """Run (req, now) pairs through oracle and kernel; compare each step.

    The kernel side runs the whole sequence in ONE dispatch via decide_scan
    (stacked (T, 1) batches), so long fuzz sequences don't pay per-step
    dispatch overhead.
    """
    import dataclasses

    import jax

    K = get_kernels(layout)

    oracle = OracleEngine()
    wants = []
    for r, now in seq:
        want = oracle.decide(dataclasses.replace(r), now)
        wants.append(
            (int(want.status), int(want.limit), int(want.remaining), int(want.reset_time))
        )

    batches = [
        encode_batch([dataclasses.replace(r)], now, num_groups, 1) for r, now in seq
    ]
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
    nows = np.array([now for _, now in seq], dtype=np.int64)
    table = K.create(num_groups, WAYS)
    _, outs = K.decide_scan(table, stacked, nows, WAYS, False)

    for i, (r, _) in enumerate(seq):
        got = (
            int(outs.status[i, 0]),
            int(outs.limit[i, 0]),
            int(outs.remaining[i, 0]),
            int(outs.reset_time[i, 0]),
        )
        assert got == wants[i], f"step {i}: {r} got={got} want={wants[i]}"


@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_token_basic(layout):
    r = lambda **kw: RateLimitReq(  # noqa: E731
        name="t", unique_key="k", algorithm=Algorithm.TOKEN_BUCKET,
        duration=5, limit=2, hits=1, **kw,
    )
    seq = [(r(), NOW), (r(), NOW), (r(), NOW + 100)]
    check_seq(seq, layout=layout)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_leaky_table(layout):
    r = lambda h: RateLimitReq(  # noqa: E731
        name="l", unique_key="k", algorithm=Algorithm.LEAKY_BUCKET,
        duration=30 * SECOND, limit=10, hits=h,
    )
    now = NOW
    seq = []
    for h, sleep in [(1, 1000), (1, 1000), (1, 1500), (0, 3000), (0, 0),
                     (9, 0), (1, 3000), (0, 60_000), (0, 60_000),
                     (10, 29_000), (9, 3000), (1, 1000)]:
        seq.append((r(h), now))
        now += sleep
    check_seq(seq, layout=layout)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_behaviors(layout):
    def mk(**kw):
        kw.setdefault("duration", 30_000)
        kw.setdefault("limit", 10)
        return RateLimitReq(name="b", unique_key="k", **kw)
    seq = [
        (mk(hits=10), NOW),
        (mk(hits=1), NOW),  # over limit, sticky status
        (mk(hits=0, behavior=Behavior.RESET_REMAINING), NOW),  # frees slot
        (mk(hits=1), NOW + 10),
        (mk(hits=100, behavior=Behavior.DRAIN_OVER_LIMIT), NOW + 20),
        (mk(hits=0), NOW + 30),
        # algorithm switch resets
        (mk(hits=1, algorithm=Algorithm.LEAKY_BUCKET), NOW + 40),
        (mk(hits=1), NOW + 50),
        # limit change credit
        (mk(hits=1, limit=20), NOW + 60),
        # duration change + renewal
        (mk(hits=1, limit=20, duration=10), NOW + 40_000),
    ]
    check_seq(seq, layout=layout)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_gregorian(layout):
    mk = lambda **kw: RateLimitReq(  # noqa: E731
        name="g", unique_key="k",
        behavior=Behavior.DURATION_IS_GREGORIAN,
        duration=GREGORIAN_MINUTES, limit=60, **kw,
    )
    start = (NOW // 60_000) * 60_000 + 100
    seq = [
        (mk(hits=1), start),
        (mk(hits=1, algorithm=Algorithm.LEAKY_BUCKET), start + 500),
        (mk(hits=1, algorithm=Algorithm.LEAKY_BUCKET), start + 1700),
        (mk(hits=58), start + 2000),
        (mk(hits=0), start + 61_000),
    ]
    check_seq(seq, layout=layout)


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_kernel_fuzz(seed, layout):
    rng = random.Random(seed)
    keys = [f"acct:{i}" for i in range(25)]
    names = ["rl_a", "rl_b"]
    now = NOW
    seq = []
    for _ in range(700):
        behavior = 0
        if rng.random() < 0.08:
            behavior |= Behavior.RESET_REMAINING
        if rng.random() < 0.15:
            behavior |= Behavior.DRAIN_OVER_LIMIT
        if rng.random() < 0.10:
            behavior |= Behavior.DURATION_IS_GREGORIAN
        greg = behavior & Behavior.DURATION_IS_GREGORIAN
        r = RateLimitReq(
            name=rng.choice(names),
            unique_key=rng.choice(keys),
            algorithm=rng.choice([Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]),
            behavior=behavior,
            duration=rng.choice([GREGORIAN_MINUTES, GREGORIAN_HOURS_SAFE])
            if greg
            else rng.choice([0, 5, 100, 1000, 30_000, 60_000]),
            limit=rng.choice([0, 1, 2, 10, 100, 2000]),
            hits=rng.choice([-5, -1, 0, 1, 1, 1, 2, 5, 10, 99, 3000]),
            burst=rng.choice([0, 0, 0, 5, 15, 30]),
        )
        seq.append((r, now))
        now += rng.choice([0, 0, 1, 7, 50, 500, 3000, 61_000])
    check_seq(seq, layout=layout)


GREGORIAN_HOURS_SAFE = 1  # GREGORIAN_HOURS


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("seed", [100, 104])
def test_kernel_fuzz_adversarial(seed, layout):
    """Extreme domain (caught an oracle/kernel int64-wrap divergence in
    round 1): 2^40 durations, +/-2^30 hits, 2^31-1 limits, huge bursts."""
    rng = random.Random(seed)
    keys = [f"acct:{i}" for i in range(30)]
    now = NOW
    seq = []
    for _ in range(500):
        behavior = 0
        if rng.random() < 0.08:
            behavior |= Behavior.RESET_REMAINING
        if rng.random() < 0.15:
            behavior |= Behavior.DRAIN_OVER_LIMIT
        if rng.random() < 0.10:
            behavior |= Behavior.DURATION_IS_GREGORIAN
        greg = behavior & Behavior.DURATION_IS_GREGORIAN
        seq.append(
            (
                RateLimitReq(
                    name=rng.choice(["a", "b"]),
                    unique_key=rng.choice(keys),
                    algorithm=rng.choice(
                        [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                    ),
                    behavior=behavior,
                    duration=rng.choice([GREGORIAN_MINUTES, 1])
                    if greg
                    else rng.choice([0, 3, 1000, 30_000, 2**40]),
                    limit=rng.choice([0, 1, 10, 2000, 2**31 - 1]),
                    hits=rng.choice([-(2**30), -1, 0, 1, 5, 3000, 2**30]),
                    burst=rng.choice([0, 5, 30, 2**30]),
                ),
                now,
            )
        )
        now += rng.choice([0, 1, 50, 3000, 61_000, 10**7])
    check_seq(seq, layout=layout)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_batch_parallel_lanes(layout):
    """Multiple distinct-group keys decided in one batched call must match
    per-key sequential oracle results."""
    oracle = OracleEngine()
    kern = KernelHarness(batch=16, layout=layout)
    reqs = [
        RateLimitReq(
            name="batch", unique_key=f"k{i}", algorithm=Algorithm.TOKEN_BUCKET,
            duration=60_000, limit=10, hits=i % 4,
        )
        for i in range(12)
    ]
    groups = set()
    from gubernator_tpu.api.keys import group_of, key_hash128

    for r in reqs:
        g = group_of(key_hash128(r.hash_key())[1], NUM_GROUPS)
        assert g not in groups, "test requires distinct groups; adjust keys"
        groups.add(g)

    import dataclasses

    b = encode_batch([dataclasses.replace(r) for r in reqs], NOW, NUM_GROUPS, 16)
    kern.table, out = kern.K.decide(kern.table, b, NOW, WAYS, False)
    for i, r in enumerate(reqs):
        want = oracle.decide(dataclasses.replace(r), NOW)
        got = (int(out.status[i]), int(out.limit[i]), int(out.remaining[i]), int(out.reset_time[i]))
        assert got == (want.status, want.limit, want.remaining, want.reset_time), i
    # padding lanes untouched
    assert int(out.limit[15]) == 0


# ---------------------------------------------------------------------------
# Paged addressing layer (ops/paged.py): the paged table must be a
# bit-exact twin of the flat table whenever the touched pages are
# resident — scrambled physical placement and demote/promote churn
# included. The flat kernel is the oracle here (it is itself pinned to
# OracleEngine by every test above).
# ---------------------------------------------------------------------------

GROUPS_PER_PAGE = 32  # 512 groups -> 16 logical pages


def _fuzz_reqs(seed, n=300):
    rng = random.Random(seed)
    keys = [f"acct:{i}" for i in range(25)]
    now = NOW
    seq = []
    for _ in range(n):
        behavior = 0
        if rng.random() < 0.08:
            behavior |= Behavior.RESET_REMAINING
        if rng.random() < 0.15:
            behavior |= Behavior.DRAIN_OVER_LIMIT
        r = RateLimitReq(
            name=rng.choice(["rl_a", "rl_b"]),
            unique_key=rng.choice(keys),
            algorithm=rng.choice(
                [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
            ),
            behavior=behavior,
            duration=rng.choice([0, 5, 100, 1000, 30_000, 60_000]),
            limit=rng.choice([0, 1, 2, 10, 100, 2000]),
            hits=rng.choice([-5, -1, 0, 1, 1, 1, 2, 5, 10, 99, 3000]),
            burst=rng.choice([0, 0, 0, 5, 15, 30]),
        )
        seq.append((r, now))
        now += rng.choice([0, 0, 1, 7, 50, 500, 3000, 61_000])
    return seq


def _assert_outs_equal(of, op, i, layout):
    for f in ("status", "limit", "remaining", "reset_time",
              "evicted_hi", "evicted_lo", "freed"):
        got = np.asarray(getattr(op, f))
        want = np.asarray(getattr(of, f))
        assert (got == want).all(), (
            f"paged/{layout} step {i} field {f}: got={got} want={want}"
        )


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("seed", [11, 12])
def test_paged_bitexact_all_resident(seed, layout):
    """Full fuzz sequence, every page resident but SCRAMBLED across the
    physical table: logical->physical translation must be invisible."""
    import dataclasses

    import jax

    from gubernator_tpu.ops.kernels import get_paged_kernels

    K = get_kernels(layout)
    PK = get_paged_kernels(layout, NUM_GROUPS, WAYS, GROUPS_PER_PAGE, 16)
    pt = PK.create()
    perm = list(range(PK.num_logical_pages))
    random.Random(seed).shuffle(perm)
    for lp, pp in enumerate(perm):
        pt = PK.bind_page(pt, np.int32(lp), np.int32(pp))

    seq = _fuzz_reqs(seed)
    batches = [
        encode_batch([dataclasses.replace(r)], now, NUM_GROUPS, 1)
        for r, now in seq
    ]
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
    nows = np.array([now for _, now in seq], dtype=np.int64)
    flat = K.create(NUM_GROUPS, WAYS)
    _, of = K.decide_scan(flat, stacked, nows, WAYS, False)
    _, op = PK.decide_scan(pt, stacked, nows, WAYS, False)
    _assert_outs_equal(of, op, "scan", layout)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_paged_bitexact_under_churn(layout):
    """Demand paging with fewer physical frames than logical pages: each
    step promotes the touched page (demoting the LRU victim through a
    host-side row store, exactly the runtime pager's dance) and must
    still match the flat table bit-for-bit — demote -> promote is an
    identity on counter state."""
    import dataclasses

    import jax

    from gubernator_tpu.ops.kernels import get_paged_kernels

    K = get_kernels(layout)
    PK = get_paged_kernels(layout, NUM_GROUPS, WAYS, GROUPS_PER_PAGE, 4)
    pt = PK.create()
    flat = K.create(NUM_GROUPS, WAYS)

    host_tier = {}  # logical page -> wide rows (numpy)
    resident = {}  # logical page -> physical page
    free = list(range(PK.num_phys_pages))
    lru = {}

    seq = _fuzz_reqs(31, n=160)
    for i, (r, now) in enumerate(seq):
        b = encode_batch([dataclasses.replace(r)], now, NUM_GROUPS, 1)
        lp = int(b.group[0]) // GROUPS_PER_PAGE
        if lp not in resident:
            if free:
                pp = free.pop()
            else:
                victim = min(resident, key=lambda p: lru[p])
                pp = resident.pop(victim)
                rows = jax.tree.map(
                    np.asarray, PK.extract_page(pt, np.int32(pp))
                )
                host_tier[victim] = rows
                pt = PK.unbind_page(pt, np.int32(victim), np.int32(pp))
            if lp in host_tier:
                pt = PK.write_page(
                    pt, np.int32(lp), np.int32(pp), host_tier.pop(lp)
                )
            else:
                pt = PK.bind_page(pt, np.int32(lp), np.int32(pp))
            resident[lp] = pp
        lru[lp] = i
        flat, of = K.decide(flat, b, now, WAYS, False)
        pt, op = PK.decide(pt, b, now, WAYS, False)
        _assert_outs_equal(of, op, i, layout)
    assert host_tier or len(resident) == PK.num_phys_pages


@pytest.mark.parametrize("layout", LAYOUTS)
def test_paged_nonresident_probe_safe(layout):
    """A probe/decide against a demoted page must not corrupt resident
    state: gathers clamp (no spurious match), scatters drop."""
    from gubernator_tpu.ops.kernels import get_paged_kernels

    PK = get_paged_kernels(layout, NUM_GROUPS, WAYS, GROUPS_PER_PAGE, 2)
    pt = PK.create()
    pt = PK.bind_page(pt, np.int32(0), np.int32(0))

    import dataclasses

    import jax
    import jax.numpy as jnp

    # Seed a key on resident page 0 by scanning unique_keys.
    resident_req = None
    demoted_req = None
    for i in range(200):
        r = RateLimitReq(
            name="pg", unique_key=f"k{i}", duration=60_000, limit=10, hits=1
        )
        b = encode_batch([dataclasses.replace(r)], NOW, NUM_GROUPS, 1)
        lp = int(b.group[0]) // GROUPS_PER_PAGE
        if lp == 0 and resident_req is None:
            resident_req = (r, b)
        elif lp != 0 and demoted_req is None:
            demoted_req = (r, b)
        if resident_req and demoted_req:
            break
    rr, rb = resident_req
    dr, db = demoted_req
    pt, _ = PK.decide(pt, rb, NOW, WAYS, False)
    before = np.asarray(PK.to_wide(pt).remaining).copy()
    # Hammer the demoted page: decide + probe must be inert.
    pt, out = PK.decide(pt, db, NOW + 1, WAYS, False)
    exists = PK.probe_exists(
        pt,
        jnp.asarray(db.key_hi),
        jnp.asarray(db.key_lo),
        jnp.asarray(db.group),
        NOW + 2,
        WAYS,
    )
    assert not bool(np.asarray(exists)[0])
    after = np.asarray(PK.to_wide(pt).remaining)
    assert (before == after).all(), "non-resident decide mutated the table"
    # The resident key is still served with its counter intact.
    pt, out = PK.decide(pt, rb, NOW + 3, WAYS, False)
    assert int(out.remaining[0]) == 8


# ---------------------------------------------------------------------------
# Admission accounting (ops/admission.py): the jitted scan must be a
# bit-exact twin of the numpy oracle over the same table state — every
# layout, fuzz-built tables at several expiry horizons, injected debt
# (negative remaining, the only state that can show excess), and the
# paged table's device-frames + host-tier split (the engine's own
# decomposition in _admission_scan).
# ---------------------------------------------------------------------------

from gubernator_tpu.ops.admission import admission_oracle, make_admission  # noqa: E402
from gubernator_tpu.ops.kernels import get_raw_kernels  # noqa: E402
from gubernator_tpu.ops.layout import SlotTable  # noqa: E402

_ADMISSION_SUMS = (
    "keys", "admitted_sum", "limit_sum", "excess_sum",
    "excess_keys", "over_limit_keys",
)


def _admission_assert(out, want, ctx):
    for f in _ADMISSION_SUMS + ("max_excess",):
        assert int(np.asarray(getattr(out, f))) == int(want[f]), (f, ctx)
    got_hist = np.asarray(out.excess_hist).tolist()
    assert got_hist == np.asarray(want["excess_hist"]).tolist(), ctx


def _fuzz_table(layout, seed):
    """Final table state after a fuzz sequence, plus the last `now`."""
    import dataclasses

    import jax

    K = get_kernels(layout)
    seq = _fuzz_reqs(seed)
    batches = [
        encode_batch([dataclasses.replace(r)], now, NUM_GROUPS, 1)
        for r, now in seq
    ]
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
    nows = np.array([now for _, now in seq], dtype=np.int64)
    table, _ = K.decide_scan(K.create(NUM_GROUPS, WAYS), stacked, nows, WAYS, False)
    return table, int(nows[-1])


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("seed", [21, 22])
def test_admission_bitexact_fuzz(seed, layout):
    """Device scan == oracle on a fuzz-built table, at `now` horizons
    that slide the active set from everything to nothing (the
    expire_at > now filter is part of the contract)."""
    table, last = _fuzz_table(layout, seed)
    RK = get_raw_kernels(layout)
    prog = make_admission(layout, WAYS)
    for now in (NOW, last, last + 61_000, last + 10**9):
        out = prog(table, now)
        want = admission_oracle(RK.to_wide(table), now)
        _admission_assert(out, want, (layout, seed, now))
    # the far horizon really deactivated everything
    assert int(np.asarray(prog(table, last + 10**9).keys)) == 0


@pytest.mark.parametrize("layout", LAYOUTS)
def test_admission_bitexact_injected_debt(layout):
    """Excess accounting: kernels never drive `remaining` negative, so
    debt (reconciled/injected state) is planted through the layout's
    from_wide. Token slots carry raw hit debt, leaky slots Q44.20 —
    the scan must agree with the oracle on sums, max, and histogram."""
    table, last = _fuzz_table(layout, 21)
    RK = get_raw_kernels(layout)
    wide = RK.to_wide(table)
    w = {f: np.asarray(getattr(wide, f)).copy() for f in SlotTable._fields}
    rng = np.random.default_rng(7)
    idx = np.flatnonzero(w["used"] & (w["limit"] > 0))
    assert idx.size >= 8, "fuzz table too sparse for debt injection"
    pick = rng.choice(idx, size=8, replace=False)
    debt = rng.integers(1, 1 << 20, size=8).astype(np.int64)
    w["remaining"][pick] = np.where(
        w["algo"][pick] == 1, -(debt << 20), -debt
    )
    # keep the debtors in the current window — expired debt is invisible
    # to the scan by design
    w["expire_at"][pick] = last + 100_000
    injected = RK.from_wide(SlotTable(**w))
    # the layout must round-trip negative remaining losslessly
    assert (
        np.asarray(RK.to_wide(injected).remaining)[pick]
        == w["remaining"][pick]
    ).all(), f"{layout}: from_wide lost injected debt"
    out = make_admission(layout, WAYS)(injected, last)
    want = admission_oracle(SlotTable(**w), last)
    assert want["excess_sum"] >= int(debt.sum()), "injection had no effect"
    assert sum(want["excess_hist"][1:]) == 8
    _admission_assert(out, want, (layout, "debt"))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_admission_paged_tiers_bitexact(layout):
    """The engine's paged split: admission-scan the resident physical
    frames on device, oracle the demoted host pages, and the combined
    tiers must equal the flat twin's totals bit-for-bit (each key lives
    in exactly one tier)."""
    import dataclasses

    import jax

    from gubernator_tpu.ops.kernels import get_paged_kernels

    K = get_kernels(layout)
    RK = get_raw_kernels(layout)
    PK = get_paged_kernels(layout, NUM_GROUPS, WAYS, GROUPS_PER_PAGE, 4)
    pt = PK.create()
    flat = K.create(NUM_GROUPS, WAYS)

    host_tier = {}
    resident = {}
    free = list(range(PK.num_phys_pages))
    lru = {}
    seq = _fuzz_reqs(31, n=160)
    # Long-window tail: the fuzz clock jumps past every short duration,
    # so without these the active set at `last` is empty and the
    # additivity check would be vacuous.
    tail_now = seq[-1][1]
    seq += [
        (
            RateLimitReq(
                name="rl_tail", unique_key=f"acct:{i}",
                duration=600_000, limit=100, hits=3,
            ),
            tail_now,
        )
        for i in range(16)
    ]
    for i, (r, now) in enumerate(seq):
        b = encode_batch([dataclasses.replace(r)], now, NUM_GROUPS, 1)
        lp = int(b.group[0]) // GROUPS_PER_PAGE
        if lp not in resident:
            if free:
                pp = free.pop()
            else:
                victim = min(resident, key=lambda p: lru[p])
                pp = resident.pop(victim)
                host_tier[victim] = jax.tree.map(
                    np.asarray, PK.extract_page(pt, np.int32(pp))
                )
                pt = PK.unbind_page(pt, np.int32(victim), np.int32(pp))
            if lp in host_tier:
                pt = PK.write_page(
                    pt, np.int32(lp), np.int32(pp), host_tier.pop(lp)
                )
            else:
                pt = PK.bind_page(pt, np.int32(lp), np.int32(pp))
            resident[lp] = pp
        lru[lp] = i
        flat, _ = K.decide(flat, b, now, WAYS, False)
        pt, _ = PK.decide(pt, b, now, WAYS, False)
    last = seq[-1][1]
    assert host_tier, "churn never demoted a page; shrink the frame count"

    # Device tier: the jitted scan over the resident frames (repacked
    # through from_wide, the same raw-layout view the engine scans).
    frames_wide = PK.to_wide(pt)
    frames = RK.from_wide(
        jax.tree.map(lambda x: np.asarray(x), frames_wide)
    )
    dev = make_admission(layout, WAYS)(frames, last)
    dev_want = admission_oracle(frames_wide, last)
    _admission_assert(dev, dev_want, (layout, "frames"))

    # Host tier: oracle over the concatenated demoted rows.
    lps = sorted(host_tier)
    host_wide = SlotTable(
        **{
            f: np.concatenate(
                [np.asarray(getattr(host_tier[lp], f)) for lp in lps]
            )
            for f in SlotTable._fields
        }
    )
    host_want = admission_oracle(host_wide, last)

    # Tier additivity == the flat twin's truth.
    flat_want = admission_oracle(RK.to_wide(flat), last)
    for f in _ADMISSION_SUMS:
        assert int(np.asarray(getattr(dev, f))) + host_want[f] == flat_want[f], f
    assert max(
        int(np.asarray(dev.max_excess)), host_want["max_excess"]
    ) == flat_want["max_excess"]
    combined = (
        np.asarray(dev.excess_hist) + np.asarray(host_want["excess_hist"])
    ).tolist()
    assert combined == np.asarray(flat_want["excess_hist"]).tolist()
    assert flat_want["keys"] > 0  # the comparison wasn't vacuous


@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_eviction_lru(layout):
    """Group overflow evicts the least-recently-used way
    (reference lrucache.go:138-161 policy, per group)."""
    kern = KernelHarness(num_groups=1, ways=2, batch=1, layout=layout)
    mk = lambda k, h=1: RateLimitReq(  # noqa: E731
        name="e", unique_key=k, duration=60_000, limit=10, hits=h,
    )
    kern.decide_one(mk("a"), NOW)  # slot 0
    kern.decide_one(mk("b"), NOW + 1)  # slot 1
    kern.decide_one(mk("a"), NOW + 2)  # touch a -> b is LRU
    kern.decide_one(mk("c"), NOW + 3)  # evicts b
    # a retains state (2 hits so far)
    s, lim, rem, _ = kern.decide_one(mk("a"), NOW + 4)
    assert rem == 10 - 3
    # b was evicted: fresh bucket
    s, lim, rem, _ = kern.decide_one(mk("b"), NOW + 5)
    assert rem == 9


# ---------------------------------------------------------------------------
# Pallas fused decide (ops/pallas_decide.py): the one-HBM-pass kernel
# must be a bit-exact twin of the XLA decide path it replaces — same
# outputs, same table mutations — across both pallas layouts, flat AND
# paged (including scrambled page maps, sentinel non-resident lanes,
# and scatter-drop), and its fused admission/census side-output must
# match the standalone scans. On CPU these run the interpret and
# reference lowerings; the mosaic path shares _wave_compute with both.
# ---------------------------------------------------------------------------

import os  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from gubernator_tpu.ops import pallas_decide as _pd  # noqa: E402
from gubernator_tpu.ops.census import census_oracle  # noqa: E402
from gubernator_tpu.ops.layout import RequestBatch  # noqa: E402
from gubernator_tpu.ops.paged import make_paged_kernels  # noqa: E402

# GL014 kernel-parity registry: every decide* entry point wired through
# ops/kernels.py / ops/paged.py must name its oracle-comparison test
# here. guberlint parses this dict from disk and fails the build when a
# new entry point lands without a parity case (or maps to a test that
# does not exist in this file).
KERNEL_PARITY_CASES = {
    # wide + per-layout XLA impls: oracle fuzz over every registry layout
    "decide": "test_kernel_fuzz",
    "decide_scan": "test_kernel_fuzz",
    "decide_packed": "test_kernel_fuzz",
    "decide_scan_packed": "test_kernel_fuzz",
    "decide_fused": "test_kernel_fuzz",
    "decide_scan_fused": "test_kernel_fuzz",
    "decide_narrow": "test_kernel_fuzz",
    "decide_scan_narrow": "test_kernel_fuzz",
    # pallas flat facades: differential vs the XLA kernels above
    "decide_flat": "test_pallas_flat_bitexact",
    "decide_scan_flat": "test_pallas_scan_bitexact",
    # pallas paged facades: in-kernel page translation vs translate+XLA
    "decide_paged": "test_pallas_paged_bitexact",
    "decide_scan_paged": "test_pallas_paged_scan_bitexact",
}

PALLAS_LAYOUTS = list(_pd.PALLAS_LAYOUTS)
# reference = plain-XLA fused program (the non-TPU serving lowering);
# interpret = pl.pallas_call(interpret=True), the real kernel body.
PALLAS_MODES = ("reference", "interpret")
_PB = 64  # lanes per fuzz wave
_PGPP, _NPP = 32, 8  # 512 logical groups -> 16 pages, 8 resident

_PALLAS_OUT_FIELDS = (
    "status", "limit", "remaining", "reset_time", "slot", "freed",
    "hits", "misses", "over_limit", "evicted_hi", "evicted_lo",
    "unexpired_evictions",
)


def _pallas_reqs(rng, now, num_groups=NUM_GROUPS):
    """One fuzz wave as a raw RequestBatch (the assembler's output
    shape), with the distinct-active-groups invariant enforced."""
    b = _PB
    ki = rng.integers(0, 200, size=b)
    hi = np.asarray(
        [(int(k) * 2654435761) % (1 << 62) for k in ki], dtype=np.int64
    )
    lo = np.asarray(
        [(int(k) * 1140071481932319848) % (1 << 62) for k in ki],
        dtype=np.int64,
    )
    batch = RequestBatch(
        key_hi=jnp.asarray(hi, jnp.int64),
        key_lo=jnp.asarray(lo, jnp.int64),
        group=jnp.asarray((ki % num_groups).astype(np.int32)),
        algo=jnp.asarray(rng.choice([0, 1], size=b).astype(np.int8)),
        behavior=jnp.asarray(
            rng.choice(
                [0, int(Behavior.RESET_REMAINING),
                 int(Behavior.DRAIN_OVER_LIMIT)],
                size=b,
            ).astype(np.int32)
        ),
        hits=jnp.asarray(rng.integers(1, 5, size=b), jnp.int64),
        limit=jnp.asarray(rng.integers(1, 100, size=b), jnp.int64),
        duration=jnp.asarray(rng.integers(1000, 60000, size=b), jnp.int64),
        rate_num=jnp.asarray(rng.integers(1, 100, size=b), jnp.int64),
        eff_duration=jnp.asarray(
            rng.integers(1000, 60000, size=b), jnp.int64
        ),
        greg_expire=jnp.asarray(np.full(b, now + 60000), jnp.int64),
        burst=jnp.asarray(rng.integers(1, 100, size=b), jnp.int64),
        created_at=jnp.asarray(np.full(b, now), jnp.int64),
        active=jnp.asarray(rng.random(b) < 0.9),
    )
    return _dedupe_groups(batch)


def _dedupe_groups(batch):
    """Deactivate duplicate-group lanes (assembler invariant: one
    active lane per group per wave)."""
    seen = set()
    act = np.asarray(batch.active).copy()
    for i, g in enumerate(np.asarray(batch.group)):
        if act[i]:
            if int(g) in seen:
                act[i] = False
            else:
                seen.add(int(g))
    return batch._replace(active=jnp.asarray(act))


def _assert_outs_match(ox, op, tag, fields=_PALLAS_OUT_FIELDS):
    for f in fields:
        av, bv = np.asarray(getattr(ox, f)), np.asarray(getattr(op, f))
        assert np.array_equal(av, bv), (
            f"{tag}: field {f} diverged\nxla={av}\npallas={bv}"
        )


def _assert_tables_match(tx, tp, tag):
    for lx, lp in zip(jax.tree.leaves(tx), jax.tree.leaves(tp)):
        assert np.array_equal(np.asarray(lx), np.asarray(lp)), (
            f"{tag}: table leaf diverged"
        )


def _set_pallas_mode(monkeypatch, mode):
    monkeypatch.setenv(
        "GUBER_PALLAS_INTERPRET", "1" if mode == "interpret" else "0"
    )


@pytest.mark.parametrize("mode", PALLAS_MODES)
@pytest.mark.parametrize("layout", PALLAS_LAYOUTS)
def test_pallas_flat_bitexact(layout, mode, monkeypatch):
    """decide_flat vs the XLA decide kernel: outputs AND every table
    leaf bit-equal across a multi-wave fuzz sequence."""
    _set_pallas_mode(monkeypatch, mode)
    monkeypatch.setenv("GUBER_KERNEL", "xla")
    K = get_kernels(layout)
    rng = np.random.default_rng(7)
    tx = K.create(NUM_GROUPS, WAYS)
    tp = K.create(NUM_GROUPS, WAYS)
    for step in range(4):
        t = NOW + step * 500
        b = _pallas_reqs(rng, t)
        tx, ox = K.decide(tx, b, jnp.int64(t), WAYS)
        tp, op = _pd.decide_flat(tp, b, jnp.int64(t), layout=layout, ways=WAYS)
        _assert_outs_match(ox, op, f"{layout}/{mode}/step{step}")
        _assert_tables_match(tx, tp, f"{layout}/{mode}/step{step}")


@pytest.mark.parametrize("layout", PALLAS_LAYOUTS)
def test_pallas_registry_routing(layout, monkeypatch):
    """GUBER_KERNEL=pallas swaps decide/decide_scan in the registry —
    and the swapped facade still matches the XLA twin (the serving path
    the engine actually builds)."""
    monkeypatch.setenv("GUBER_PALLAS_INTERPRET", "0")
    monkeypatch.setenv("GUBER_KERNEL", "xla")
    Kx = get_kernels(layout)
    monkeypatch.setenv("GUBER_KERNEL", "pallas")
    Kp = get_kernels(layout)
    assert Kx.decide is not Kp.decide
    rng = np.random.default_rng(11)
    tx, tp = Kx.create(NUM_GROUPS, WAYS), Kp.create(NUM_GROUPS, WAYS)
    for step in range(3):
        t = NOW + step * 500
        b = _pallas_reqs(rng, t)
        tx, ox = Kx.decide(tx, b, jnp.int64(t), WAYS)
        tp, op = Kp.decide(tp, b, jnp.int64(t), WAYS)
        _assert_outs_match(ox, op, f"routing/{layout}/step{step}")
        _assert_tables_match(tx, tp, f"routing/{layout}/step{step}")


@pytest.mark.parametrize("mode", PALLAS_MODES)
@pytest.mark.parametrize("layout", PALLAS_LAYOUTS)
def test_pallas_scan_bitexact(layout, mode, monkeypatch):
    """decide_scan_flat vs the XLA decide_scan: stacked multi-wave
    parity (outputs per step + final table)."""
    _set_pallas_mode(monkeypatch, mode)
    monkeypatch.setenv("GUBER_KERNEL", "xla")
    K = get_kernels(layout)
    rng = np.random.default_rng(13)
    steps = 3
    waves = [_pallas_reqs(rng, NOW + i * 500) for i in range(steps)]
    batches = jax.tree.map(lambda *xs: jnp.stack(xs), *waves)
    nows = jnp.asarray([NOW + i * 500 for i in range(steps)], jnp.int64)
    tx, ox = K.decide_scan(K.create(NUM_GROUPS, WAYS), batches, nows, WAYS)
    tp, op = _pd.decide_scan_flat(
        K.create(NUM_GROUPS, WAYS), batches, nows, layout=layout, ways=WAYS
    )
    _assert_outs_match(ox, op, f"scan/{layout}/{mode}")
    _assert_tables_match(tx, tp, f"scan/{layout}/{mode}")


def _paged_pair(layout, monkeypatch, scramble=(3, 1, 7, 0, 5, 2, 6, 4)):
    """XLA and pallas paged kernel sets over identically-bound tables:
    logical pages 0..7 scrambled across physical frames."""
    monkeypatch.setenv("GUBER_KERNEL", "xla")
    PKx = make_paged_kernels(layout, NUM_GROUPS, WAYS, _PGPP, _NPP)
    monkeypatch.setenv("GUBER_KERNEL", "pallas")
    PKp = make_paged_kernels(layout, NUM_GROUPS, WAYS, _PGPP, _NPP)
    ptx, ptp = PKx.create(), PKp.create()
    for lp, pp in enumerate(scramble):
        ptx = PKx.bind_page(ptx, lp, pp)
        ptp = PKp.bind_page(ptp, lp, pp)
    return PKx, PKp, ptx, ptp


@pytest.mark.parametrize("mode", PALLAS_MODES)
@pytest.mark.parametrize("layout", PALLAS_LAYOUTS)
def test_pallas_paged_bitexact(layout, mode, monkeypatch):
    """decide_paged (in-kernel page_map translation) vs the XLA
    translate-then-decide path, scrambled page map, all lanes resident."""
    _set_pallas_mode(monkeypatch, mode)
    PKx, PKp, ptx, ptp = _paged_pair(layout, monkeypatch)
    rng = np.random.default_rng(17)
    for step in range(4):
        t = NOW + step * 500
        b = _pallas_reqs(rng, t)  # keys mod 200 -> all groups resident
        ptx, ox = PKx.decide(ptx, b, jnp.int64(t), WAYS)
        ptp, op = PKp.decide(ptp, b, jnp.int64(t), WAYS)
        _assert_outs_match(ox, op, f"paged/{layout}/{mode}/step{step}")
        _assert_tables_match(ptx, ptp, f"paged/{layout}/{mode}/step{step}")


@pytest.mark.parametrize("mode", PALLAS_MODES)
@pytest.mark.parametrize("layout", PALLAS_LAYOUTS)
def test_pallas_paged_scan_bitexact(layout, mode, monkeypatch):
    """decide_scan_paged vs the XLA paged scan over stacked waves."""
    _set_pallas_mode(monkeypatch, mode)
    PKx, PKp, ptx, ptp = _paged_pair(layout, monkeypatch)
    rng = np.random.default_rng(19)
    steps = 3
    waves = [_pallas_reqs(rng, NOW + i * 500) for i in range(steps)]
    batches = jax.tree.map(lambda *xs: jnp.stack(xs), *waves)
    nows = jnp.asarray([NOW + i * 500 for i in range(steps)], jnp.int64)
    ptx, ox = PKx.decide_scan(ptx, batches, nows, WAYS)
    ptp, op = PKp.decide_scan(ptp, batches, nows, WAYS)
    _assert_outs_match(ox, op, f"paged-scan/{layout}/{mode}")
    _assert_tables_match(ptx, ptp, f"paged-scan/{layout}/{mode}")


@pytest.mark.parametrize("mode", PALLAS_MODES)
@pytest.mark.parametrize("layout", PALLAS_LAYOUTS)
def test_pallas_paged_sentinel_scatter_drop(layout, mode, monkeypatch):
    """Lanes whose group lives on a NON-resident page must drop their
    scatter entirely: sentinel slot >= n, page_map untouched, every
    table leaf inert, and the response fields the server surfaces
    (fresh-bucket semantics) still bit-match the XLA paged path."""
    _set_pallas_mode(monkeypatch, mode)
    PKx, PKp, ptx, ptp = _paged_pair(layout, monkeypatch)
    rng = np.random.default_rng(23)
    b = _pallas_reqs(rng, NOW)
    # shift every even lane onto pages 8..15 (non-resident)
    grp = np.asarray(b.group)
    resident_groups = _NPP * _PGPP  # 256
    shifted = np.where(
        np.arange(_PB) % 2 == 0,
        grp % resident_groups + resident_groups,
        grp % resident_groups,
    ).astype(np.int32)
    b = _dedupe_groups(b._replace(group=jnp.asarray(shifted)))
    t = jnp.int64(NOW + 99_000)
    ptx2, ox = PKx.decide(ptx, b, t, WAYS)
    ptp2, op = PKp.decide(ptp, b, t, WAYS)
    act = np.asarray(b.active)
    nonres = act & (np.asarray(b.group) >= resident_groups)
    assert nonres.sum() > 0, "fuzz must hit non-resident pages"
    n = _NPP * _PGPP * WAYS
    assert (np.asarray(op.slot)[nonres] >= n).all(), "sentinel slot < n"
    # response fields are garbage-independent on sentinel lanes (the
    # kernel zeroes the probe rows -> deterministic fresh-bucket reply);
    # evicted_hi/lo and slot are the documented sentinel divergence.
    _assert_outs_match(
        ox, op, f"sentinel/{layout}/{mode}",
        fields=("status", "limit", "remaining", "reset_time", "freed"),
    )
    # resident lanes wrote; non-resident frames stayed inert — compare
    # only the frames no resident lane touched, via the XLA twin.
    _assert_tables_match(ptx2, ptp2, f"sentinel/{layout}/{mode}")
    # a wave of ONLY non-resident lanes must leave the table untouched
    # (snapshot first: the decide facades donate the table buffers)
    snap = [np.asarray(x).copy() for x in jax.tree.leaves(ptp2)]
    only_nonres = b._replace(
        active=jnp.asarray(act & (np.asarray(b.group) >= resident_groups))
    )
    ptp3, _ = PKp.decide(ptp2, only_nonres, t + 1, WAYS)
    for before, after in zip(
        snap,
        [np.asarray(x) for x in jax.tree.leaves(ptp3)],
    ):
        assert np.array_equal(before, after), (
            f"sentinel/{layout}/{mode}: non-resident wave mutated table"
        )


@pytest.mark.parametrize("mode", PALLAS_MODES)
@pytest.mark.parametrize("layout", PALLAS_LAYOUTS)
def test_pallas_wavescan_matches_scans(layout, mode, monkeypatch):
    """The fused admission/census side-output must equal the standalone
    scans run over exactly the rows the wave wrote."""
    _set_pallas_mode(monkeypatch, mode)
    monkeypatch.setenv("GUBER_KERNEL", "xla")
    K = get_kernels(layout)
    rng = np.random.default_rng(29)
    tp = K.create(NUM_GROUPS, WAYS)
    for step in range(3):
        t = NOW + step * 500
        b = _pallas_reqs(rng, t)
        tp, out, scan = _pd.decide_flat_with_scan(
            tp, b, jnp.int64(t), layout=layout, ways=WAYS
        )
        rows = K.gather_rows(tp, out.slot)
        adm = admission_oracle(rows, t)
        cen = census_oracle(rows, t, ways=1)
        tag = f"wavescan/{layout}/{mode}/step{step}"
        assert int(scan.adm_keys) == int(adm["keys"]), tag
        assert int(scan.adm_admitted) == int(adm["admitted_sum"]), tag
        assert int(scan.adm_limit) == int(adm["limit_sum"]), tag
        assert int(scan.census_live) == int(cen["live"]), tag
        assert int(scan.census_waste) == int(cen["waste"]), tag
