"""Paged slot table at the ENGINE level (docs/architecture.md "Paged
table"): serving through the indirection map must be bit-exact with the
flat table, demote/promote must lose nothing — including across
snapshot/restore and ownership handover — and promotion must be safe
against concurrent flushes (it runs under the same engine lock).

The ops-level twin (scrambled placement, demand-paging churn vs the
flat kernel oracle, both layouts) lives in tests/test_kernel_fuzz.py;
here the flat DeviceEngine is the oracle.
"""

import dataclasses
import random
import threading

import numpy as np
import pytest

from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq, Status
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig
from gubernator_tpu.runtime.pager import PageBudgetError
from gubernator_tpu.utils import lockorder

# Direct-Pager tests poke fields the engine normally touches under its
# table lock. The race sanitizer (tests/conftest.py) checks locks by
# NAME, so holding any lock named "engine.table" satisfies the Pager's
# guarded-by declarations here.
_TABLE_LOCK = lockorder.make_lock("engine.table")

NOW = 1_753_700_000_000

NUM_GROUPS = 256
PAGE_GROUPS = 32  # -> 8 logical pages


def mk(key="k", **kw):
    kw.setdefault("name", "pg")
    kw.setdefault("duration", 60_000)
    kw.setdefault("limit", 100)
    kw.setdefault("hits", 1)
    return RateLimitReq(unique_key=key, **kw)


def make_engine(page_budget=0, page_groups=0, layout="fused", now_fn=None,
                **kw):
    kw.setdefault("num_groups", NUM_GROUPS)
    kw.setdefault("batch_size", 64)
    kw.setdefault("batch_wait_s", 0.001)
    kw.setdefault("page_demote_interval_s", 0)  # deterministic tests
    return DeviceEngine(
        EngineConfig(
            layout=layout, page_groups=page_groups,
            page_budget=page_budget, **kw,
        ),
        now_fn=now_fn or (lambda: NOW),
    )


def tup(rl):
    return (rl.status, rl.limit, rl.remaining, rl.reset_time, rl.error)


def _fuzz_reqs(seed, n=120, keys=20):
    rng = random.Random(seed)
    names = ["rl_a", "rl_b"]
    out = []
    for _ in range(n):
        behavior = 0
        if rng.random() < 0.1:
            behavior |= Behavior.RESET_REMAINING
        out.append(
            RateLimitReq(
                name=rng.choice(names),
                unique_key=f"acct:{rng.randrange(keys)}",
                algorithm=rng.choice(
                    [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                ),
                behavior=behavior,
                duration=rng.choice([5_000, 60_000, 600_000]),
                limit=rng.choice([1, 10, 100]),
                hits=rng.choice([0, 1, 1, 2, 5, 50, 200]),
                burst=rng.choice([0, 0, 10]),
            )
        )
    return out


# ---------------------------------------------------------------------------
# bit-exactness vs the flat engine


@pytest.mark.parametrize("page_groups", [PAGE_GROUPS, 8])
def test_paged_engine_matches_flat(page_groups):
    """Same request stream, small mixed batches: a fully-resident paged
    engine and demand-paged engine (budget 2 of the 8, or 32, pages)
    must both answer exactly like the flat engine."""
    reqs = _fuzz_reqs(7)
    flat = make_engine()
    resident = make_engine(
        page_groups=page_groups, page_budget=NUM_GROUPS // page_groups
    )
    # budget=2: single-key batches so one wave never exceeds the budget
    paged = make_engine(page_groups=page_groups, page_budget=2)
    try:
        for i in range(0, len(reqs), 4):
            chunk = [dataclasses.replace(r) for r in reqs[i:i + 4]]
            want = [tup(r) for r in flat.check_batch(chunk)]
            got_res = [
                tup(r) for r in resident.check_batch(
                    [dataclasses.replace(r) for r in chunk]
                )
            ]
            assert got_res == want, f"resident diverged at chunk {i}"
            got_paged = []
            for r in chunk:  # one key per flush: wave fits budget 2
                got_paged.append(
                    tup(paged.check_batch([dataclasses.replace(r)])[0])
                )
            assert got_paged == want, f"demand-paged diverged at chunk {i}"
        pager = paged._pager
        assert pager.demotes > 0 and pager.promotes > 0, (
            "budget 2 never cycled — the test isn't "
            "exercising demand paging"
        )
    finally:
        flat.close()
        resident.close()
        paged.close()


def test_keyspace_beyond_resident_budget_zero_loss():
    """Keyspace spanning all 8 logical pages served through 2 resident
    frames: every key's counter stays exact through demote/promote."""
    eng = make_engine(page_groups=PAGE_GROUPS, page_budget=2)
    try:
        keys = [f"cap:{i}" for i in range(48)]
        for _ in range(5):
            for k in keys:
                rl = eng.check_batch([mk(key=k)])[0]
                assert rl.error == "" and rl.status == Status.UNDER_LIMIT
        for k in keys:
            rl = eng.check_batch([mk(key=k, hits=0)])[0]
            assert rl.remaining == 95, (k, rl.remaining)
        pager = eng._pager
        with eng._lock:
            assert pager.resident_count() <= 2
            assert pager.demotes >= pager.host_count() > 0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# census + budget errors


def test_census_reports_tiers_and_page_map():
    eng = make_engine(page_groups=PAGE_GROUPS, page_budget=2)
    try:
        for i in range(32):
            eng.check_batch([mk(key=f"cen:{i}")])
        c = eng.table_census(max_age_s=0)
        tiers = c["tiers"]
        assert set(tiers) >= {"device", "host"}
        assert int(tiers["host"]["live"]) > 0, "no page was ever demoted"
        assert int(c["live"]) == int(tiers["device"]["live"]) + int(
            tiers["host"]["live"]
        ) == 32
        pages = c["pages"]
        assert pages["enabled"] is True
        assert pages["groups_per_page"] == PAGE_GROUPS
        assert pages["logical_pages"] == NUM_GROUPS // PAGE_GROUPS
        assert pages["budget"] == 2
        assert pages["resident"] + pages["free"] == 2
        with eng._lock:
            assert pages["host"] == eng._pager.host_count() > 0
        assert pages["demotes"] > 0
    finally:
        eng.close()


def test_one_wave_over_budget_raises_loudly():
    """A single wave touching more distinct pages than the budget can
    hold must raise PageBudgetError (silently dropping lanes would
    serve wrong decisions), naming the knob to raise."""
    eng = make_engine(page_groups=PAGE_GROUPS, page_budget=2)
    try:
        with pytest.raises(PageBudgetError, match="GUBER_TABLE_PAGE_BUDGET"):
            with eng._lock:
                eng._pager.ensure_resident(
                    eng.table, np.arange(4, dtype=np.int64)
                )
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# snapshot / restore / handover across demoted pages


def _serve_and_demote(eng, n_keys=40, hits_rounds=3):
    keys = [f"snap:{i}" for i in range(n_keys)]
    for _ in range(hits_rounds):
        for k in keys:
            eng.check_batch([mk(key=k)])
    return keys


def test_snapshot_equals_flat_and_restores_across_budgets():
    """The paged snapshot is the LOGICAL wide image: identical to the
    flat engine's snapshot for the same traffic, and restorable into a
    SMALLER budget with zero loss (overflow pages land in the host
    tier)."""
    flat = make_engine()
    paged = make_engine(page_groups=PAGE_GROUPS, page_budget=2)
    try:
        for eng in (flat, paged):
            _serve_and_demote(eng)
        s_flat, s_paged = flat.snapshot(), paged.snapshot()
        assert s_flat.keys() == s_paged.keys()
        for f in s_flat:
            if f == "key_strings":
                assert s_flat[f] == s_paged[f]
            else:
                assert np.array_equal(
                    np.asarray(s_flat[f]), np.asarray(s_paged[f])
                ), f"snapshot field {f} diverges from the flat engine"
    finally:
        flat.close()

    # restore the paged image into an even tighter engine: 8 live pages
    # through 1 resident frame
    tight = make_engine(page_groups=PAGE_GROUPS, page_budget=1)
    try:
        tight.restore(s_paged)
        with tight._lock:
            host_n = tight._pager.host_count()
        assert host_n > 0, (
            "restore fit everything resident — budget isn't tight"
        )
        for i in range(40):
            rl = tight.check_batch([mk(key=f"snap:{i}", hits=0)])[0]
            assert rl.remaining == 97, (i, rl.remaining)
    finally:
        tight.close()


def test_handover_exports_keys_on_demoted_pages():
    """TransferSnapshots (Loader.Save feed) drains through snapshot(),
    so keys whose page sits in the host-DRAM tier must still hand over
    — and merge into a flat receiver with their exact counters."""
    from gubernator_tpu.store.store import (
        merge_snapshots_lww,
        snapshots_from_engine,
    )

    src = make_engine(page_groups=PAGE_GROUPS, page_budget=2)
    dst = make_engine()
    try:
        keys = _serve_and_demote(src)
        with src._lock:
            assert src._pager.host_count() > 0
        items = {s.key for s in snapshots_from_engine(src)}
        missing = [k for k in keys if f"pg_{k}" not in items]
        assert not missing, f"demoted keys absent from handover: {missing}"

        accepted, stale = merge_snapshots_lww(
            dst, snapshots_from_engine(src)
        )
        assert accepted == len(keys) and stale == 0
        for k in keys:
            rl = dst.check_batch([mk(key=k, hits=0)])[0]
            assert rl.remaining == 97, (k, rl.remaining)
    finally:
        src.close()
        dst.close()


# ---------------------------------------------------------------------------
# chaos: promotion racing flushes and the background demoter


@pytest.mark.chaos
def test_promotion_races_flushes_and_demoter():
    """Three serving threads (single-key flushes across all 8 logical
    pages) race a demoter thread that keeps evacuating LRU pages.
    Promotion happens inside the flush under the engine lock, so no
    interleaving may lose a hit or serve an error."""
    eng = make_engine(page_groups=PAGE_GROUPS, page_budget=4)
    keys = [f"race:{i}" for i in range(24)]
    rounds = 8
    errors = []
    stop = threading.Event()

    def serve(tid):
        try:
            for _ in range(rounds):
                for k in keys[tid::3]:
                    rl = eng.check_batch([mk(key=k)])[0]
                    if rl.error:
                        errors.append((k, rl.error))
        except Exception as e:  # pragma: no cover - failure path
            errors.append((tid, repr(e)))

    def demote_loop():
        while not stop.is_set():
            with eng._lock:
                eng.table = eng._pager.demote_victims(
                    eng.table, want_free=3
                )

    try:
        demoter = threading.Thread(target=demote_loop, daemon=True)
        demoter.start()
        threads = [
            threading.Thread(target=serve, args=(t,)) for t in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        stop.set()
        demoter.join(timeout=30)
        assert not errors, errors[:5]
        for k in keys:
            rl = eng.check_batch([mk(key=k, hits=0)])[0]
            assert rl.remaining == 100 - rounds, (k, rl.remaining)
        assert eng._pager.demotes > 0 and eng._pager.promotes > 0
    finally:
        stop.set()
        eng.close()


# ---------------------------------------------------------------------------
# demoter victim policy: census coldness first, LRU tiebreak


class _FakePK:
    """Minimal PagedKernels stand-in for Pager unit tests: positional
    page moves are identity ops on a dummy table."""

    num_logical_pages = 4
    num_phys_pages = 2
    groups_per_page = 4
    page_slots = 16

    def bind_page(self, table, lp, pp):
        return table

    def unbind_page(self, table, lp, pp):
        return table

    def write_page(self, table, lp, pp, rows):
        return table

    def extract_page(self, table, pp):
        from gubernator_tpu.ops.layout import SlotTable
        from gubernator_tpu.runtime.pager import wide_zeros

        return SlotTable(**wide_zeros(self.page_slots))


def _resident_pager():
    from gubernator_tpu.runtime.pager import Pager

    p = Pager(_FakePK())
    # bind lp 0 -> frame 0 and lp 1 -> frame 1 by hand
    with _TABLE_LOCK:
        p.page_map[0], p.page_map[1] = 0, 1
        p.free = []
    return p


def test_coldness_from_heatmap_folds_regions_to_pages():
    p = _resident_pager()
    with _TABLE_LOCK:
        # 4 groups per page, 2 groups per census region -> page 0
        # (frame 0) covers regions 0-1, page 1 (frame 1) regions 2-3
        hm = [5, 1, 0, 2]
        cold = p.coldness_from_heatmap(hm, groups_per_region=2)
        assert cold == {0: 6.0, 1: 2.0}
        # region wider than a page: overlap-weighted share
        cold = p.coldness_from_heatmap([8], groups_per_region=8)
        assert cold == {0: 4.0, 1: 4.0}


def test_census_cold_page_evicted_before_hot_touched():
    """The ISSUE-13 satellite contract: a page whose touch tick is HOT
    (a single probe just re-warmed it) but whose slots the census counts
    idle must be evicted before a census-busy page with an older touch.
    Census coldness also overrides the min_idle_ticks spare gate."""
    p = _resident_pager()
    with _TABLE_LOCK:
        p._tick = 10
        p.touch[0] = 10  # hot-touched...
        p.touch[1] = 2   # ...vs old-touched
        coldness = {0: 6.0, 1: 0.0}  # ...but census-cold vs census-busy
        assert p._pick_victim(coldness) == 0
        p.demote_victims(
            object(), want_free=1, min_idle_ticks=100, coldness=coldness
        )
        assert p.page_map[0] == -1, "census-cold page was not evicted"
        assert p.page_map[1] == 1, "census-busy page was evicted instead"
        assert p.free == [0]


def test_pure_lru_fallback_and_min_idle_spare():
    p = _resident_pager()
    with _TABLE_LOCK:
        p._tick = 10
        p.touch[0], p.touch[1] = 9, 10
        # no census signal: LRU picks the older touch
        assert p._pick_victim(None) == 0
        # both pages touched within min_idle_ticks and no census
        # coldness: the demoter must spare them all and stop
        p.demote_victims(
            object(), want_free=2, min_idle_ticks=5, coldness=None
        )
        assert p.free == [] and p.page_map[0] == 0 and p.page_map[1] == 1
        # without the idle gate the LRU victim goes
        p.demote_victims(object(), want_free=1)
        assert p.page_map[0] == -1 and p.page_map[1] == 1


def test_background_demoter_fills_free_target():
    """With the demote interval armed and traffic parked on every page,
    the background thread must evacuate down to the free-frame floor
    once the census shows the resident set has gone cold."""
    clock = {"now": NOW}
    eng = make_engine(
        page_groups=PAGE_GROUPS, page_budget=4,
        page_demote_interval_s=0.05, page_free_target=2,
        census_ttl_s=0.01, now_fn=lambda: clock["now"],
    )
    try:
        for i in range(32):
            eng.check_batch([mk(key=f"bg:{i}")])
        # jump far past every window: the census cold gate must now see
        # the whole resident set as idle and let the demoter evacuate
        clock["now"] += 100 * 60_000

        def freed():
            with eng._lock:
                return len(eng._pager.free)

        deadline = 100
        while freed() < 2 and deadline:
            threading.Event().wait(0.05)
            deadline -= 1
        assert freed() >= 2, "demoter never reached page_free_target"
        # nothing lost: every counter still answers exactly
        for i in range(32):
            rl = eng.check_batch([mk(key=f"bg:{i}", hits=0)])[0]
            assert rl.error == ""
    finally:
        eng.close()
