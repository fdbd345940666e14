"""Table census: device program vs pure-numpy oracle (bit-exact, both
layouts at 8 and 4 ways + the stacked ici-replica variant), clamp/wraparound and
expired-slot edges, determinism, the engine-side TTL cache + churn
ledger, and the scrape-never-compiles invariant the observatory is
built around (guberlint GL009; docs/monitoring.md "Table census")."""

import numpy as np
import pytest

import jax

from gubernator_tpu.api.types import RateLimitReq
from gubernator_tpu.metrics import CENSUS_BUCKETS as METRICS_CENSUS_BUCKETS
from gubernator_tpu.metrics import Metrics, engine_sync
from gubernator_tpu.ops.census import (
    CENSUS_BUCKETS,
    CensusOutput,
    census_oracle,
    make_census,
)
from gubernator_tpu.ops.kernels import LAYOUTS, get_census, get_raw_kernels
from gubernator_tpu.ops.layout import SlotTable
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig

NOW = 1_753_700_000_000
GROUPS = 64
WAYS = 8


def mk(key="k", **kw):
    kw.setdefault("name", "t")
    kw.setdefault("duration", 60_000)
    kw.setdefault("limit", 10)
    kw.setdefault("hits", 1)
    return RateLimitReq(unique_key=key, **kw)


def random_wide(rng, groups=GROUPS, ways=WAYS, density=0.5, now=NOW):
    """Random WIDE table (host numpy arrays) with adversarial time
    fields: ages up to ~weeks, future stamps (wraparound clamp), and a
    mix of expired and live windows."""
    n = groups * ways
    used = rng.random(n) < density
    z = np.zeros(n, dtype=np.int64)
    durations = rng.choice(
        np.array([1_000, 60_000, 3_600_000], dtype=np.int64), size=n
    )
    # now - stamp spans [-1h, ~2 weeks]: negative ages must clamp to 0
    stamp = now - rng.integers(-3_600_000, 1_300_000_000, size=n)
    lru = now - rng.integers(-3_600_000, 1_300_000_000, size=n)
    expire_at = now + rng.integers(-7_200_000, 7_200_000, size=n)
    return SlotTable(
        key_hi=np.where(used, rng.integers(1, 1 << 40, size=n), z),
        key_lo=np.where(used, rng.integers(1, 1 << 40, size=n), z),
        used=used,
        algo=rng.integers(0, 2, size=n).astype(np.int8),
        status=np.zeros(n, dtype=np.int8),
        limit=rng.integers(1, 1000, size=n),
        duration=durations,
        remaining=rng.integers(0, 1000, size=n),
        stamp=stamp,
        expire_at=expire_at,
        invalid_at=z,
        burst=rng.integers(0, 1000, size=n),
        lru=lru,
    )


def assert_census_equals_oracle(out: CensusOutput, want: dict):
    got = {f: np.asarray(getattr(out, f)) for f in out._fields}
    assert int(got["live"]) == want["live"]
    assert int(got["full_groups"]) == want["full_groups"]
    assert int(got["waste"]) == want["waste"]
    assert int(got["age_sum"]) == want["age_sum"]
    assert int(got["idle_sum"]) == want["idle_sum"]
    assert int(got["max_full_run"]) == want["max_full_run"]
    for field in (
        "age_hist", "idle_hist", "heatmap", "fill_hist", "cold",
        "cold_heatmap",
    ):
        np.testing.assert_array_equal(got[field], want[field], err_msg=field)


# ---- kernel vs oracle -------------------------------------------------------


@pytest.mark.parametrize("ways", [WAYS, 4])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_census_bit_exact_vs_oracle(layout, ways):
    rng = np.random.default_rng(0xCE)
    RK = get_raw_kernels(layout)
    census = get_census(layout, ways, heatmap_width=16)
    for trial in range(4):
        wide = random_wide(
            rng, ways=ways, density=(0.1, 0.5, 0.9, 1.0)[trial]
        )
        table = RK.to_wide(RK.from_wide(wide))  # oracle sees the exact
        out = census(RK.from_wide(wide), NOW)  # logical table the
        want = census_oracle(  # device scans
            jax.tree.map(np.asarray, table),
            NOW,
            ways=ways,
            heatmap_width=16,
        )
        assert_census_equals_oracle(out, want)


@pytest.mark.parametrize("ways", [WAYS, 4])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_census_stacked_replica_tier_matches_flat(layout, ways):
    """The ici replica tier's stacked=True variant scans replica 0 of a
    (D, ...) stacked table — identical output to the flat program."""
    rng = np.random.default_rng(7)
    RK = get_raw_kernels(layout)
    wide = random_wide(rng, groups=16, ways=ways, density=0.6)
    table = RK.from_wide(wide)
    stacked = jax.tree.map(
        lambda x: np.stack([np.asarray(x)] * 2), table
    )
    flat = get_census(layout, ways, heatmap_width=8)(table, NOW)
    rep = get_census(layout, ways, heatmap_width=8, stacked=True)(
        stacked, NOW
    )
    for f in flat._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(flat, f)), np.asarray(getattr(rep, f)),
            err_msg=f,
        )


def test_census_empty_and_full_tables():
    census = get_census("wide", WAYS, heatmap_width=16)
    empty = SlotTable.create(GROUPS, WAYS)
    out = census(empty, NOW)
    assert int(np.asarray(out.live)) == 0
    assert int(np.asarray(out.waste)) == 0
    assert int(np.asarray(out.max_full_run)) == 0
    assert np.asarray(out.age_hist).sum() == 0
    assert int(np.asarray(out.fill_hist)[0]) == GROUPS

    rng = np.random.default_rng(3)
    full = random_wide(rng, density=1.1)  # every slot used
    out = census(full, NOW)
    assert int(np.asarray(out.live)) == GROUPS * WAYS
    assert int(np.asarray(out.full_groups)) == GROUPS
    assert int(np.asarray(out.max_full_run)) == GROUPS
    assert int(np.asarray(out.fill_hist)[WAYS]) == GROUPS


def test_census_clamps_and_buckets():
    """Hand-built table pinning the binning contract: bin 0 is < 1ms,
    bin i is [2^(i-1), 2^i) ms, future stamps clamp to bin 0 and never
    poison the sums."""
    wide = SlotTable.create(4, 2)
    wide = wide._replace(
        used=np.array([True, True, True, True, False, False, False, False]),
        key_lo=np.array([1, 2, 3, 4, 0, 0, 0, 0], dtype=np.int64),
        # ages: 0ms, 1ms, 7ms, -50ms (future stamp -> clamp)
        stamp=NOW - np.array([0, 1, 7, -50, 0, 0, 0, 0], dtype=np.int64),
        lru=np.int64(NOW) + np.zeros(8, dtype=np.int64),
        expire_at=np.int64(NOW) + np.ones(8, dtype=np.int64),
        duration=np.full(8, 60_000, dtype=np.int64),
    )
    out = get_census("wide", 2, heatmap_width=4)(wide, NOW)
    age = np.asarray(out.age_hist)
    assert age[0] == 2  # the 0ms and clamped-future slots
    assert age[1] == 1  # 1ms -> [1, 2)
    assert age[3] == 1  # 7ms -> [4, 8)
    assert int(np.asarray(out.age_sum)) == 0 + 1 + 7 + 0
    want = census_oracle(wide, NOW, ways=2, heatmap_width=4)
    assert_census_equals_oracle(out, want)


def test_census_determinism():
    """Same table, same now -> byte-identical census (the snapshot is a
    pure function: safe to diff across replicas or over time)."""
    rng = np.random.default_rng(11)
    wide = random_wide(rng)
    census = get_census("fused", WAYS)
    table = get_raw_kernels("fused").from_wide(wide)
    a = census(table, NOW)
    b = census(table, NOW)
    for f in a._fields:
        assert (
            np.asarray(getattr(a, f)).tobytes()
            == np.asarray(getattr(b, f)).tobytes()
        ), f


def test_metrics_bucket_constant_in_lockstep():
    # metrics.py mirrors the bucket count as a literal (it must stay
    # jax-free); this is the lockstep pin
    assert METRICS_CENSUS_BUCKETS == CENSUS_BUCKETS


# ---- engine wiring ----------------------------------------------------------


@pytest.fixture
def engine():
    clock = {"now": NOW}
    eng = DeviceEngine(
        EngineConfig(num_groups=1 << 10, batch_size=64, batch_wait_s=0.002),
        now_fn=lambda: clock["now"],
    )
    eng._test_clock = clock
    yield eng
    eng.close()


def test_engine_census_snapshot_and_views(engine):
    engine.check_batch([mk(f"k{i}") for i in range(32)])
    c = engine.table_census(max_age_s=0)
    assert c["v"] == 1
    assert c["live"] == 32
    assert c["slots"] == (1 << 10) * 8
    assert c["occupancy"] == pytest.approx(32 / c["slots"])
    assert sum(c["age_ms_hist"]) == 32 and sum(c["idle_ms_hist"]) == 32
    assert len(c["age_ms_hist"]) == CENSUS_BUCKETS
    assert sum(c["heatmap"]) == 32
    assert len(c["heatmap"]) == 64
    assert c["waste"] == 0  # nothing expired yet
    assert [e["multiplier"] for e in c["cold"]] == [1, 4, 16]
    assert c["cold"][0]["reclaimable_bytes"] == (
        c["cold"][0]["slots"] * c["bytes_per_slot"]
    )
    assert set(c["tiers"]) == {"device"}
    # back-compat views read the same cache
    assert engine.live_count() == 32
    stats = engine.occupancy_stats()
    assert set(stats) == {"live", "slots", "occupancy", "full_group_ratio"}
    assert stats["live"] == 32


def test_engine_census_sees_expiry(engine):
    engine.check_batch([mk(f"e{i}", duration=1_000) for i in range(8)])
    engine._test_clock["now"] = NOW + 3_600_000
    c = engine.table_census(max_age_s=0)
    assert c["waste"] == 8  # expired but still resident
    assert c["cold"][-1]["slots"] == 8  # idle >> 16x their duration


def test_census_ttl_cache(engine):
    engine.check_batch([mk(f"t{i}") for i in range(4)])
    a = engine.table_census()
    assert engine.table_census() is a  # inside TTL: cached object
    b = engine.table_census(max_age_s=0)  # forced fresh
    assert b is not a
    assert engine.table_census() is b  # fresh scan repopulated cache


def test_churn_ledger(engine):
    engine.check_batch([mk(f"c{i}") for i in range(16)])
    first = engine.table_census(max_age_s=0)["churn"]
    assert first["insertions"] == 0  # no prior interval to diff against
    engine.check_batch([mk(f"c{i}") for i in range(16)])  # 16 hits
    engine.check_batch([mk(f"n{i}") for i in range(8)])  # 8 inserts
    churn = engine.table_census(max_age_s=0)["churn"]
    assert churn["insertions"] == 8
    assert churn["evictions"] == 0
    assert churn["overwrite_recycles"] == 0  # live grew by exactly 8
    assert churn["interval_s"] > 0
    assert churn["insert_per_s"] > 0


def test_churn_ledger_counts_recycles(engine):
    engine.check_batch([mk(f"r{i}", duration=1_000) for i in range(8)])
    engine.table_census(max_age_s=0)
    engine._test_clock["now"] = NOW + 3_600_000
    # same groups, new identities: inserts reclaim the expired slots
    engine.check_batch([mk(f"r{i}", duration=1_000) for i in range(8)])
    churn = engine.table_census(max_age_s=0)["churn"]
    assert churn["insertions"] == 8
    # every insert that didn't grow `live` recycled a dead resident
    assert churn["overwrite_recycles"] == 8 - max(
        engine.table_census()["live"] - 8, 0
    ) - churn["evictions"]


def test_scraping_under_load_never_compiles(engine):
    """The acceptance pin: serving traffic while /metrics + /debug/table
    consumers hammer the census keeps cold compiles at ZERO (warmup
    compiled the census program) and the pump keeps flushing."""
    m = Metrics()
    m.add_sync(engine_sync(engine))
    engine.check_batch([mk(f"w{i}") for i in range(50)])
    for i in range(5):
        engine.check_batch([mk(f"l{i}_{j}") for j in range(20)])
        c = engine.table_census(max_age_s=0)  # /debug/table, forced cold
        assert c["live"] > 0
        m.render()  # /metrics exposition path incl. census gauges
        engine.hotkeys_snapshot()  # /debug/hotkeys join
    assert engine.metrics.cold_compiles == 0
    flushes = [
        r for r in engine.metrics.recorder.snapshot() if r.get("n")
    ]
    assert len(flushes) >= 6  # the pump kept serving throughout
    text = m.render().decode()
    assert "gubernator_table_slot_age_seconds_bucket" in text
    assert "gubernator_table_slots" in text


def test_hotkeys_census_join(engine):
    engine.check_batch([mk(f"h{i}") for i in range(12)])
    snap = engine.hotkeys_snapshot()
    assert snap["entries"]
    assert snap["cold_multiplier"] == 4
    assert {e["census"] for e in snap["entries"]} == {"resident"}
    # expire everything: the join reclassifies without new traffic
    engine._test_clock["now"] = NOW + 3_600_000
    snap = engine.hotkeys_snapshot()
    assert {e["census"] for e in snap["entries"]} == {"expired"}


# ---- ici tier ---------------------------------------------------------------


def test_ici_census_combines_tiers():
    from gubernator_tpu.api.types import Behavior
    from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig

    eng = IciEngine(
        IciEngineConfig(
            num_groups=1 << 9, num_slots=1 << 11, batch_size=64,
            batch_wait_s=0.002, sync_wait_s=3600,
        ),
        now_fn=lambda: NOW,
    )
    try:
        eng.check_batch(
            [mk(f"g{i}", behavior=Behavior.GLOBAL) for i in range(10)]
            + [mk(f"s{i}") for i in range(10)]
        )
        eng.sync_now()
        c = eng.table_census(max_age_s=0)
        assert set(c["tiers"]) == {"sharded", "replica"}
        assert c["slots"] == (
            c["tiers"]["sharded"]["slots"] + c["tiers"]["replica"]["slots"]
        )
        # additive fields sum across tiers
        assert c["live"] == (
            c["tiers"]["sharded"]["live"] + c["tiers"]["replica"]["live"]
        )
        assert c["live"] >= 20
        assert sum(c["age_ms_hist"]) == c["live"]
        # structural fields come from the primary (sharded) tier
        assert c["layout"] == c["tiers"]["sharded"]["layout"]
        assert c["heatmap"] == c["tiers"]["sharded"]["heatmap"]
        # the old occupancy_stats() shape is preserved
        stats = eng.occupancy_stats()
        assert stats["slots"] == (1 << 9) * 8 + (1 << 11)
        assert eng.metrics.cold_compiles == 0
    finally:
        eng.close()
