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
from gubernator_tpu.ops.layout import batch_entry, run_entry
from gubernator_tpu.utils.gregorian import GREGORIAN_MINUTES

NOW = 1_753_700_000_000
NUM_GROUPS = 512

# Every golden/fuzz case runs against BOTH table layouts (the
# ops/kernels.py registry), each at the two group widths the benchmark's
# cells run: 8 ways (the sharded and single-chip tables) and 4 ways (the
# GLOBAL replica tier, where two groups share one fused line). All must
# be bit-exact twins of the oracle.
from gubernator_tpu.ops.kernels import LAYOUTS  # noqa: E402

LAYOUTS = list(LAYOUTS)
WAYS = [8, 4]
# How a sequence reaches the program, both as an engine launches it: one
# launch a step, or the steps stacked into one run (ops/layout.py
# packed_waves: the loop over waves, its early stop, the packed output).
RUNS = ["per_wave", "stacked"]


def packed(K, ways, with_store=False):
    """`K`'s launched entry (Kernels or PagedKernels) as (table, operand)."""
    return lambda t, op: K.decide_packed(t, op, ways, with_store)


def run_depth(steps: int) -> int:
    """A stacked depth that holds `steps` waves with room to spare (a
    power of two, at least 16): the sequences of this file compile a few
    depths between them, and every run ends in empty waves, as a run an
    engine pads to a compiled depth does."""
    return max(16, 1 << steps.bit_length())


def decide_seq(entry, table, steps, run="stacked", with_store=False):
    """`steps` [(batch, now)] through `entry` (table, operand), one
    launch a step or as one stacked run: (table, [DecideOutput])."""
    if run == "per_wave":
        step, outs = batch_entry(entry, with_store), []
        for batch, now in steps:
            table, out = step(table, batch, now)
            outs.append(out)
        return table, outs
    assert run == "stacked", run
    table, outs, vecs = run_entry(entry, with_store)(
        table, steps, run_depth(len(steps))
    )
    # the loop stopped at the last wave with a lane: the padding's rows
    # were never written
    assert not vecs[len(steps):].any()
    return table, outs


class KernelHarness:
    """Single-request-per-call harness around the launched entry."""

    def __init__(self, num_groups=NUM_GROUPS, ways=8, batch=1, layout="wide"):
        self.K = get_kernels(layout)
        self.table = self.K.create(num_groups, ways)
        self.num_groups = num_groups
        self.ways = ways
        self.batch = batch
        self.step = batch_entry(packed(self.K, ways))

    def decide_one(self, r: RateLimitReq, now_ms: int):
        import copy

        rc = copy.replace(r) if hasattr(copy, "replace") else r
        b = encode_batch([rc], now_ms, self.num_groups, self.batch)
        self.table, out = self.step(self.table, b, now_ms)
        return (
            int(out.status[0]),
            int(out.limit[0]),
            int(out.remaining[0]),
            int(out.reset_time[0]),
        )


def encode_steps(seq, num_groups=NUM_GROUPS):
    """(req, now) pairs as one-lane steps [(RequestBatch, now)]."""
    import dataclasses

    return [
        (encode_batch([dataclasses.replace(r)], now, num_groups, 1), now)
        for r, now in seq
    ]


def check_seq(seq, num_groups=NUM_GROUPS, layout="wide", ways=8, run="stacked"):
    """Run (req, now) pairs through oracle and kernel; compare each step.

    The kernel side is the entry every engine launches (decide_packed),
    one launch a step (`run="per_wave"`) or the whole sequence as ONE
    stacked (T, OPERAND_ROWS, 1) run (`run="stacked"`).
    """
    import dataclasses

    K = get_kernels(layout)

    oracle = OracleEngine()
    wants = []
    for r, now in seq:
        want = oracle.decide(dataclasses.replace(r), now)
        wants.append(
            (int(want.status), int(want.limit), int(want.remaining), int(want.reset_time))
        )

    _, outs = decide_seq(
        packed(K, ways), K.create(num_groups, ways),
        encode_steps(seq, num_groups), run,
    )

    for i, (r, _) in enumerate(seq):
        got = (
            int(outs[i].status[0]),
            int(outs[i].limit[0]),
            int(outs[i].remaining[0]),
            int(outs[i].reset_time[0]),
        )
        assert got == wants[i], f"step {i}: {r} got={got} want={wants[i]}"


@pytest.mark.parametrize("ways", WAYS)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_token_basic(layout, ways):
    r = lambda **kw: RateLimitReq(  # noqa: E731
        name="t", unique_key="k", algorithm=Algorithm.TOKEN_BUCKET,
        duration=5, limit=2, hits=1, **kw,
    )
    seq = [(r(), NOW), (r(), NOW), (r(), NOW + 100)]
    check_seq(seq, layout=layout, ways=ways)


@pytest.mark.parametrize("ways", WAYS)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_leaky_table(layout, ways):
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
    check_seq(seq, layout=layout, ways=ways)


@pytest.mark.parametrize("ways", WAYS)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_behaviors(layout, ways):
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
    check_seq(seq, layout=layout, ways=ways)


@pytest.mark.parametrize("ways", WAYS)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_gregorian(layout, ways):
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
    check_seq(seq, layout=layout, ways=ways)


def _fuzz_seq(seed):
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
    return seq


GREGORIAN_HOURS_SAFE = 1  # GREGORIAN_HOURS


@pytest.mark.parametrize("run", RUNS)
@pytest.mark.parametrize("ways", WAYS)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_kernel_fuzz(seed, layout, ways, run):
    check_seq(_fuzz_seq(seed), layout=layout, ways=ways, run=run)


# (groups, ways) whose slot count is no multiple of 8: a fused line then
# holds gcd(N, 8) = 4, 2 or 1 slots (ops/fused.py _per_line), and the
# gather, the window mask and the scatter-add all take that width.
LINE_GEOMETRIES = {4: (511, 4), 2: (511, 6), 1: (511, 5)}


@pytest.mark.parametrize("per_line", sorted(LINE_GEOMETRIES, reverse=True))
@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_kernel_fuzz_line_widths(seed, per_line):
    from gubernator_tpu.ops.fused import _per_line

    groups, ways = LINE_GEOMETRIES[per_line]
    assert _per_line(groups * ways) == per_line
    check_seq(_fuzz_seq(seed), num_groups=groups, layout="fused", ways=ways)


@pytest.mark.parametrize("ways", WAYS)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("seed", [100, 104])
def test_kernel_fuzz_adversarial(seed, layout, ways):
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
    check_seq(seq, layout=layout, ways=ways)


@pytest.mark.parametrize("ways", WAYS)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_batch_parallel_lanes(layout, ways):
    """Multiple distinct-group keys decided in one batched call must match
    per-key sequential oracle results."""
    oracle = OracleEngine()
    kern = KernelHarness(batch=16, layout=layout, ways=ways)
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
    kern.table, out = kern.step(kern.table, b, NOW)
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

# (ways, groups a page): 512 groups make 16 pages of 32 groups or 32
# pages of 16; a page of 16 four-way groups is 8 fused lines.
PAGED = pytest.mark.parametrize(
    "ways,gpp",
    [pytest.param(8, 32, id="8x32"), pytest.param(4, 16, id="4x16")],
)


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


@PAGED
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("seed", [11, 12])
def test_paged_bitexact_all_resident(seed, layout, ways, gpp):
    """Full fuzz sequence, every page resident but SCRAMBLED across the
    physical table: logical->physical translation must be invisible."""
    from gubernator_tpu.ops.kernels import get_paged_kernels

    K = get_kernels(layout)
    PK = get_paged_kernels(layout, NUM_GROUPS, ways, gpp, NUM_GROUPS // gpp)
    pt = PK.create()
    perm = list(range(PK.num_logical_pages))
    random.Random(seed).shuffle(perm)
    for lp, pp in enumerate(perm):
        pt = PK.bind_page(pt, np.int32(lp), np.int32(pp))

    steps = encode_steps(_fuzz_reqs(seed))
    flat = K.create(NUM_GROUPS, ways)
    _, ofs = decide_seq(packed(K, ways, True), flat, steps, with_store=True)
    _, ops = decide_seq(packed(PK, ways, True), pt, steps, with_store=True)
    for i, (of, op) in enumerate(zip(ofs, ops)):
        _assert_outs_equal(of, op, i, layout)


@PAGED
@pytest.mark.parametrize("layout", LAYOUTS)
def test_paged_bitexact_under_churn(layout, ways, gpp):
    """Demand paging with fewer physical frames than logical pages: each
    step promotes the touched page (demoting the LRU victim through a
    host-side row store, exactly the runtime pager's dance) and must
    still match the flat table bit-for-bit — demote -> promote is an
    identity on counter state."""
    import dataclasses

    import jax

    from gubernator_tpu.ops.kernels import get_paged_kernels

    K = get_kernels(layout)
    PK = get_paged_kernels(layout, NUM_GROUPS, ways, gpp, 4)
    pt = PK.create()
    flat = K.create(NUM_GROUPS, ways)
    flat_step = batch_entry(packed(K, ways, True), True)
    paged_step = batch_entry(packed(PK, ways, True), True)

    host_tier = {}  # logical page -> wide rows (numpy)
    resident = {}  # logical page -> physical page
    free = list(range(PK.num_phys_pages))
    lru = {}

    seq = _fuzz_reqs(31, n=160)
    for i, (r, now) in enumerate(seq):
        b = encode_batch([dataclasses.replace(r)], now, NUM_GROUPS, 1)
        lp = int(b.group[0]) // gpp
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
        flat, of = flat_step(flat, b, now)
        pt, op = paged_step(pt, b, now)
        _assert_outs_equal(of, op, i, layout)
    assert host_tier or len(resident) == PK.num_phys_pages


@PAGED
@pytest.mark.parametrize("layout", LAYOUTS)
def test_paged_nonresident_probe_safe(layout, ways, gpp):
    """A probe/decide against a demoted page must not corrupt resident
    state: gathers clamp (no spurious match), scatters drop."""
    from gubernator_tpu.ops.kernels import get_paged_kernels
    from gubernator_tpu.ops.layout import WaveOperand

    PK = get_paged_kernels(layout, NUM_GROUPS, ways, gpp, 2)
    pt = PK.create()
    pt = PK.bind_page(pt, np.int32(0), np.int32(0))
    step = batch_entry(packed(PK, ways))

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
        lp = int(b.group[0]) // gpp
        if lp == 0 and resident_req is None:
            resident_req = (r, b)
        elif lp != 0 and demoted_req is None:
            demoted_req = (r, b)
        if resident_req and demoted_req:
            break
    rr, rb = resident_req
    dr, db = demoted_req
    pt, _ = step(pt, rb, NOW)
    before = np.asarray(PK.to_wide(pt).remaining).copy()
    # Hammer the demoted page: decide + probe must be inert.
    pt, out = step(pt, db, NOW + 1)
    exists = PK.probe_exists(
        pt, jnp.asarray(WaveOperand.of(db, NOW + 2).buf), ways
    )
    assert not bool(np.asarray(exists)[0])
    after = np.asarray(PK.to_wide(pt).remaining)
    assert (before == after).all(), "non-resident decide mutated the table"
    # The resident key is still served with its counter intact.
    pt, out = step(pt, rb, NOW + 3)
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


def _fuzz_table(layout, seed, ways):
    """Final table state after a fuzz sequence, plus the last `now`."""
    K = get_kernels(layout)
    steps = encode_steps(_fuzz_reqs(seed))
    table, _ = decide_seq(packed(K, ways), K.create(NUM_GROUPS, ways), steps)
    return table, steps[-1][1]


@pytest.mark.parametrize("ways", WAYS)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("seed", [21, 22])
def test_admission_bitexact_fuzz(seed, layout, ways):
    """Device scan == oracle on a fuzz-built table, at `now` horizons
    that slide the active set from everything to nothing (the
    expire_at > now filter is part of the contract)."""
    table, last = _fuzz_table(layout, seed, ways)
    RK = get_raw_kernels(layout)
    prog = make_admission(layout, ways)
    for now in (NOW, last, last + 61_000, last + 10**9):
        out = prog(table, now)
        want = admission_oracle(RK.to_wide(table), now)
        _admission_assert(out, want, (layout, ways, seed, now))
    # the far horizon really deactivated everything
    assert int(np.asarray(prog(table, last + 10**9).keys)) == 0


@pytest.mark.parametrize("ways", WAYS)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_admission_bitexact_injected_debt(layout, ways):
    """Excess accounting: kernels never drive `remaining` negative, so
    debt (reconciled/injected state) is planted through the layout's
    from_wide. Token slots carry raw hit debt, leaky slots Q44.20 —
    the scan must agree with the oracle on sums, max, and histogram."""
    table, last = _fuzz_table(layout, 21, ways)
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
    out = make_admission(layout, ways)(injected, last)
    want = admission_oracle(SlotTable(**w), last)
    assert want["excess_sum"] >= int(debt.sum()), "injection had no effect"
    assert sum(want["excess_hist"][1:]) == 8
    _admission_assert(out, want, (layout, "debt"))


@PAGED
@pytest.mark.parametrize("layout", LAYOUTS)
def test_admission_paged_tiers_bitexact(layout, ways, gpp):
    """The engine's paged split: admission-scan the resident physical
    frames on device, oracle the demoted host pages, and the combined
    tiers must equal the flat twin's totals bit-for-bit (each key lives
    in exactly one tier)."""
    import dataclasses

    import jax

    from gubernator_tpu.ops.kernels import get_paged_kernels

    K = get_kernels(layout)
    RK = get_raw_kernels(layout)
    PK = get_paged_kernels(layout, NUM_GROUPS, ways, gpp, 4)
    pt = PK.create()
    flat = K.create(NUM_GROUPS, ways)
    flat_step = batch_entry(packed(K, ways))
    paged_step = batch_entry(packed(PK, ways))

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
        lp = int(b.group[0]) // gpp
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
        flat, _ = flat_step(flat, b, now)
        pt, _ = paged_step(pt, b, now)
    last = seq[-1][1]
    assert host_tier, "churn never demoted a page; shrink the frame count"

    # Device tier: the jitted scan over the resident frames (repacked
    # through from_wide, the same raw-layout view the engine scans).
    frames_wide = PK.to_wide(pt)
    frames = RK.from_wide(
        jax.tree.map(lambda x: np.asarray(x), frames_wide)
    )
    dev = make_admission(layout, ways)(frames, last)
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


@pytest.mark.parametrize("ways", [2, 4])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_eviction_lru(layout, ways):
    """Group overflow evicts the least-recently-used way
    (reference lrucache.go:138-161 policy, per group)."""
    kern = KernelHarness(num_groups=1, ways=ways, batch=1, layout=layout)
    mk = lambda k, h=1: RateLimitReq(  # noqa: E731
        name="e", unique_key=k, duration=60_000, limit=10, hits=h,
    )
    keys = [f"k{i}" for i in range(ways)]
    now = NOW
    for k in keys:  # fill the group
        kern.decide_one(mk(k), now)
        now += 1
    for k in keys[:1] + keys[2:]:  # touch all but k1 -> k1 is LRU
        kern.decide_one(mk(k), now)
        now += 1
    kern.decide_one(mk("new"), now)  # evicts k1
    # k0 retains state (2 hits so far)
    s, lim, rem, _ = kern.decide_one(mk(keys[0]), now + 1)
    assert rem == 10 - 3
    # k1 was evicted: fresh bucket
    s, lim, rem, _ = kern.decide_one(mk(keys[1]), now + 2)
    assert rem == 9
