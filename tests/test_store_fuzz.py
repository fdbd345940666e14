"""Randomized differential fuzz of the engine WITH a Store attached
against the oracle driving the same MemoryStore: write-behind contents
and serving behavior must agree through restarts (read-through)."""

import dataclasses
import random

import pytest

from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.models.oracle import OracleEngine
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig
from gubernator_tpu.store import MemoryStore, attach_store

NOW = 1_753_700_000_000


def _engine(clock, chips):
    """The one-chip engine, or with `chips` the four-chip daemon's: the
    same Store sequence over an owner-sharded table (ISSUE 41)."""
    if not chips:
        return DeviceEngine(
            EngineConfig(num_groups=1 << 10, batch_size=32, batch_wait_s=0.001),
            now_fn=lambda: clock["now"],
        )
    import jax

    from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig

    return IciEngine(
        IciEngineConfig(
            devices=jax.devices()[:chips], num_groups=1 << 10,
            num_slots=1 << 11, batch_size=32, batch_wait_s=0.001,
            sync_wait_s=3600,
        ),
        now_fn=lambda: clock["now"],
    )


@pytest.mark.parametrize("seed,chips", [
    pytest.param(7, 0, id="7"), pytest.param(8, 0, id="8"),
    pytest.param(7, 4, id="7-four-devices"),
])
def test_engine_with_store_matches_oracle(seed, chips):
    rng = random.Random(seed)
    clock = {"now": NOW}
    eng = _engine(clock, chips)
    store = MemoryStore()
    attach_store(eng, store)
    oracle = OracleEngine()

    keys = [f"sf{i}" for i in range(12)]
    try:
        for step in range(200):
            if rng.random() < 0.1:
                clock["now"] += rng.choice([5, 500, 70_000])
            behavior = 0
            if rng.random() < 0.1:
                behavior |= Behavior.RESET_REMAINING
            if rng.random() < 0.15:
                behavior |= Behavior.DRAIN_OVER_LIMIT
            req = RateLimitReq(
                name="sf",
                unique_key=rng.choice(keys),
                algorithm=rng.choice(
                    [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                ),
                behavior=behavior,
                duration=rng.choice([100, 60_000]),
                limit=rng.choice([3, 10, 50]),
                hits=rng.choice([-1, 0, 1, 2, 5, 60]),
            )
            got = eng.check_batch([dataclasses.replace(req)])[0]
            want = oracle.decide(dataclasses.replace(req), clock["now"])
            assert (got.status, got.remaining, got.reset_time) == (
                int(want.status), want.remaining, want.reset_time
            ), f"seed {seed} step {step}: {req}"
        assert _surprises(eng) == 0

        # Restart: a fresh engine over the SAME store must continue each
        # key exactly where the oracle's state says (read-through).
        eng.close()
        eng2 = _engine(clock, chips)
        attach_store(eng2, store)
        try:
            for key in keys:
                for algo in (Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET):
                    req = RateLimitReq(
                        name="sf", unique_key=key, algorithm=algo,
                        duration=60_000, limit=50, hits=1,
                    )
                    got = eng2.check_batch([dataclasses.replace(req)])[0]
                    want = oracle.decide(dataclasses.replace(req), clock["now"])
                    assert (got.status, got.remaining) == (
                        int(want.status), want.remaining
                    ), f"seed {seed} restart key {key} algo {algo}"
        finally:
            eng2.close()
    finally:
        try:
            eng.close()
        except Exception:
            pass


def _surprises(eng) -> float:
    return eng.metrics.store_stacked_surprises.labels().get()


def _sequences(eng) -> dict:
    return {q: eng.metrics.store_flushes.labels(q).get()
            for q in ("stacked", "per_wave")}


@pytest.mark.parametrize("seed,chips", [
    pytest.param(21, 0, id="21"), pytest.param(22, 0, id="22"),
    pytest.param(23, 0, id="23"), pytest.param(21, 4, id="21-four-devices"),
])
def test_calls_of_repeated_keys_match_the_oracle_stacked_and_per_wave(
        seed, chips):
    """ISSUE 45 (e): calls of 2-24 items over twelve keys, so keys
    repeat inside a call and its waves make runs; every behaviour, both
    algorithms, changes of limit and duration, clock jumps past a
    bucket's life. The engine (stacked runs where it may) and its twin
    with no stacked Store shape warm (every flush wave by wave) both
    answer as the oracle does and end with the same Store, and
    gubernator_engine_store_stacked_surprises reads 0."""
    rng = random.Random(seed)
    clock = {"now": NOW}
    engines = [_engine(clock, chips), _engine(clock, chips)]
    stores = [MemoryStore(), MemoryStore()]
    for eng, store in zip(engines, stores):
        attach_store(eng, store)
    engines[1]._warm_store_stacks = ()
    oracle = OracleEngine()
    keys = [f"sf{i}" for i in range(12)]
    try:
        for step in range(60):
            if rng.random() < 0.3:
                clock["now"] += rng.choice([5, 500, 70_000])
            calm = rng.random() < 0.6  # a call with no RESET lane at all
            reqs = []
            for _ in range(rng.randint(2, 24)):
                behavior = 0
                if not calm and rng.random() < 0.1:
                    behavior |= Behavior.RESET_REMAINING
                if rng.random() < 0.15:
                    behavior |= Behavior.DRAIN_OVER_LIMIT
                key = rng.choice(keys)
                reqs.append(RateLimitReq(
                    name="sf", unique_key=key,
                    # a key keeps its algorithm most of the time
                    algorithm=(
                        rng.choice([Algorithm.TOKEN_BUCKET,
                                    Algorithm.LEAKY_BUCKET])
                        if rng.random() < 0.05 else
                        Algorithm(int(key[2:]) % 2)
                    ),
                    behavior=behavior,
                    duration=rng.choice([100, 60_000, 60_000, 60_000]),
                    limit=rng.choice([3, 10, 50, 50]),
                    hits=rng.choice([-1, 0, 1, 1, 2, 5, 60]),
                ))
            want = [oracle.decide(dataclasses.replace(r), clock["now"])
                    for r in reqs]
            for name, eng in zip(("stacked", "per-wave"), engines):
                got = eng.check_batch([dataclasses.replace(r) for r in reqs])
                assert [(g.status, g.remaining, g.reset_time) for g in got] == [
                    (int(w.status), w.remaining, w.reset_time) for w in want
                ], f"seed {seed} step {step} ({name})"
        assert stores[0].data == stores[1].data
        seq = _sequences(engines[0])
        assert seq["stacked"] >= 10 and seq["per_wave"] >= 10, seq
        assert _sequences(engines[1])["stacked"] == 0
        for eng in engines:
            assert _surprises(eng) == 0
            assert eng.metrics.cold_compiles == 0
    finally:
        for eng in engines:
            eng.close()


def _colliding_keys(num_groups: int, n: int, prefix: str = "ev"):
    """Find n distinct keys whose slot groups all collide (ways=1 table)."""
    from gubernator_tpu.api.keys import group_of, key_hash128

    target = None
    found = []
    i = 0
    while len(found) < n:
        k = f"{prefix}{i}"
        i += 1
        _, lo = key_hash128(f"sf_{k}")
        g = group_of(lo, num_groups)
        if target is None:
            target = g
            found.append(k)
        elif g == target:
            found.append(k)
    return found


def test_capacity_eviction_continues_from_store():
    """VERDICT r1 item 4: a key evicted from the device table under
    capacity pressure (but still known to the host dict) must re-read
    through the Store on return and CONTINUE its counter — the reference
    re-reads the store on every cache miss (algorithms.go:45-51)."""
    clock = {"now": NOW}
    eng = DeviceEngine(
        EngineConfig(num_groups=4, ways=1, batch_size=8, batch_wait_s=0.001),
        now_fn=lambda: clock["now"],
    )
    store = MemoryStore()
    attach_store(eng, store)
    oracle = OracleEngine()

    a, b = _colliding_keys(4, 2)[:2]

    def hit(key, hits=1):
        req = RateLimitReq(
            name="sf", unique_key=key, duration=600_000, limit=100, hits=hits,
        )
        got = eng.check_batch([dataclasses.replace(req)])[0]
        want = oracle.decide(dataclasses.replace(req), clock["now"])
        assert (got.status, got.remaining, got.reset_time) == (
            int(want.status), want.remaining, want.reset_time
        ), f"key {key}: {got} != {want}"
        return got

    try:
        # Consume 30 from A, then displace it with B (same group, ways=1),
        # then return to A — must resume at 70, not reset to 99.
        hit(a, 30)
        clock["now"] += 10
        hit(b, 5)  # evicts A (direct-mapped)
        clock["now"] += 10
        got = hit(a, 1)
        assert got.remaining == 69
        assert eng.metrics.unexpired_evictions >= 1
        # And the store entry for A was never deleted by the eviction.
        clock["now"] += 10
        hit(b, 1)   # evicts A again
        clock["now"] += 10
        hit(a, 4)   # back to A: 65 left
    finally:
        eng.close()


def test_eviction_interleave_fuzz_with_store():
    """Randomized interleave over a direct-mapped 4-slot table with many
    colliding keys: constant eviction pressure, every decision must still
    match the oracle (which never evicts) thanks to store read-through."""
    rng = random.Random(13)
    clock = {"now": NOW}
    eng = DeviceEngine(
        EngineConfig(num_groups=4, ways=1, batch_size=8, batch_wait_s=0.001),
        now_fn=lambda: clock["now"],
    )
    store = MemoryStore()
    attach_store(eng, store)
    oracle = OracleEngine()
    keys = _colliding_keys(4, 5)

    try:
        for step in range(150):
            if rng.random() < 0.1:
                clock["now"] += rng.choice([7, 900])
            behavior = 0
            if rng.random() < 0.08:
                behavior |= Behavior.RESET_REMAINING
            req = RateLimitReq(
                name="sf",
                unique_key=rng.choice(keys),
                algorithm=rng.choice(
                    [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                ),
                behavior=behavior,
                duration=rng.choice([100, 600_000]),
                limit=rng.choice([10, 50]),
                hits=rng.choice([0, 1, 2, 5]),
            )
            got = eng.check_batch([dataclasses.replace(req)])[0]
            want = oracle.decide(dataclasses.replace(req), clock["now"])
            assert (got.status, got.remaining, got.reset_time) == (
                int(want.status), want.remaining, want.reset_time
            ), f"step {step}: {req}"
    finally:
        eng.close()


def test_same_flush_eviction_readthrough():
    """Review finding r2: key A evicted by wave 0 of a flush that also
    contains A's own request in a later wave — A must NOT silently reset;
    the per-wave residency probe routes A through Store.Get before its
    wave decides."""
    clock = {"now": NOW}
    eng = DeviceEngine(
        EngineConfig(num_groups=4, ways=1, batch_size=8, batch_wait_s=0.05),
        now_fn=lambda: clock["now"],
    )
    store = MemoryStore()
    attach_store(eng, store)
    oracle = OracleEngine()
    a, b = _colliding_keys(4, 2)[:2]

    def mk(key, hits, behavior=0):
        return RateLimitReq(
            name="sf", unique_key=key, duration=600_000, limit=100,
            hits=hits, behavior=behavior,
        )

    try:
        # Seed A with consumed state, then evict it so only the store
        # remembers (B displaces A; drop of A from the key dict happens
        # via the eviction path).
        got = eng.check_batch([mk(a, 30)])[0]
        want = oracle.decide(mk(a, 30), clock["now"])
        assert got.remaining == want.remaining == 70
        clock["now"] += 5
        eng.check_batch([mk(b, 1)])
        oracle.decide(mk(b, 1), clock["now"])
        # Re-seed A (read-through) then submit ONE flush [B, A]: B's wave-0
        # insert displaces A again, A's wave-1 request must still continue
        # from the store, not reset to 99.
        clock["now"] += 5
        eng.check_batch([mk(a, 1)])
        oracle.decide(mk(a, 1), clock["now"])
        clock["now"] += 5
        got = eng.check_batch([mk(b, 1), mk(a, 1)])
        want_b = oracle.decide(mk(b, 1), clock["now"])
        want_a = oracle.decide(mk(a, 1), clock["now"])
        assert got[0].remaining == want_b.remaining
        assert got[1].remaining == want_a.remaining == 68
        # And the store reflects A's latest value, not a reset snapshot.
        snap = store.get(mk(a, 0))
        assert snap is not None and snap.remaining == 68
    finally:
        eng.close()


def test_same_flush_hit_then_reset_removes_store_entry():
    """Review finding r2: [hit(K), RESET_REMAINING(K)] in ONE flush must
    leave the store entry REMOVED — the batched on_change must not
    resurrect the pre-reset snapshot after the inline remove."""
    clock = {"now": NOW}
    eng = DeviceEngine(
        EngineConfig(num_groups=1 << 6, ways=4, batch_size=8, batch_wait_s=0.05),
        now_fn=lambda: clock["now"],
    )
    store = MemoryStore()
    attach_store(eng, store)
    k = "reset-key"

    def mk(hits, behavior=0):
        return RateLimitReq(
            name="sf", unique_key=k, duration=600_000, limit=100,
            hits=hits, behavior=behavior,
        )

    try:
        eng.check_batch([mk(3)])
        assert store.get(mk(0)) is not None
        # One flush: hit then RESET (two waves, same key/group).
        got = eng.check_batch(
            [mk(1), mk(1, int(Behavior.RESET_REMAINING))]
        )
        assert got[0].remaining == 96
        assert got[1].remaining == 100  # RESET response
        assert store.get(mk(0)) is None, "store entry resurrected"
        # Reverse order inside one flush: RESET then hit. K is absent (the
        # remove above), so RESET creates a new bucket consuming its hit
        # (99) and the trailing hit takes it to 98 — the final snapshot
        # must be that value, not removed.
        oracle = OracleEngine()
        want = [
            oracle.decide(mk(1, int(Behavior.RESET_REMAINING)), clock["now"]),
            oracle.decide(mk(1), clock["now"]),
        ]
        got = eng.check_batch(
            [mk(1, int(Behavior.RESET_REMAINING)), mk(1)]
        )
        assert [g.remaining for g in got] == [w.remaining for w in want] == [99, 98]
        snap = store.get(mk(0))
        assert snap is not None and snap.remaining == 98
    finally:
        eng.close()


def test_store_outage_is_a_miss_not_a_crash():
    """Review finding r2: a transient Store.get() exception must be
    treated as a cache miss — it must not fail the request and must NEVER
    wipe the device table (the donated-buffer recovery path)."""
    clock = {"now": NOW}
    eng = DeviceEngine(
        EngineConfig(num_groups=4, ways=1, batch_size=8, batch_wait_s=0.05),
        now_fn=lambda: clock["now"],
    )

    class FlakyStore(MemoryStore):
        def __init__(self):
            super().__init__()
            self.fail = False

        def get(self, req):
            if self.fail:
                raise ConnectionError("store down")
            return super().get(req)

    store = FlakyStore()
    attach_store(eng, store)
    a, b = _colliding_keys(4, 2)[:2]

    def mk(key, hits):
        return RateLimitReq(
            name="sf", unique_key=key, duration=600_000, limit=100, hits=hits,
        )

    try:
        assert eng.check_batch([mk(a, 10)])[0].remaining == 90
        store.fail = True
        # Outage during a colliding two-wave flush (read-through would
        # normally fetch): requests still serve, table survives.
        got = eng.check_batch([mk(b, 1), mk(a, 1)])
        assert got[0].error == "" and got[1].error == ""
        # a's entry was displaced by b while the store was down; with the
        # store unreachable its counter resets — the documented
        # cache-loss semantics — but b's live entry must have survived
        # (no table wipe).
        store.fail = False
        assert eng.check_batch([mk(b, 1)])[0].remaining == 98
    finally:
        eng.close()


def test_same_flush_own_hits_survive_displacement():
    """Review finding r2: one flush [A, B, A] with A,B colliding (ways=1).
    A's wave-0 hit must survive B's displacement — the wave-2 read-through
    must reuse the SAME-FLUSH decided state, not the pre-flush store
    snapshot (which would silently uncount A's first hit)."""
    clock = {"now": NOW}
    eng = DeviceEngine(
        EngineConfig(num_groups=4, ways=1, batch_size=8, batch_wait_s=0.05),
        now_fn=lambda: clock["now"],
    )
    store = MemoryStore()
    attach_store(eng, store)
    oracle = OracleEngine()
    a, b = _colliding_keys(4, 2)[:2]

    def mk(key, hits, behavior=0):
        return RateLimitReq(
            name="sf", unique_key=key, duration=600_000, limit=100,
            hits=hits, behavior=behavior,
        )

    try:
        # Seed both keys so the store has pre-flush state for each.
        eng.check_batch([mk(a, 10)])
        oracle.decide(mk(a, 10), clock["now"])
        clock["now"] += 5
        eng.check_batch([mk(b, 20)])
        oracle.decide(mk(b, 20), clock["now"])
        clock["now"] += 5
        # ONE flush, three waves: A, B, A.
        got = eng.check_batch([mk(a, 1), mk(b, 1), mk(a, 1)])
        want = [
            oracle.decide(mk(a, 1), clock["now"]),
            oracle.decide(mk(b, 1), clock["now"]),
            oracle.decide(mk(a, 1), clock["now"]),
        ]
        assert [g.remaining for g in got] == [w.remaining for w in want] == [
            89, 79, 88,
        ]
        # And the persisted value reflects BOTH of A's hits.
        snap = store.get(mk(a, 0))
        assert snap is not None and snap.remaining == 88
        # Same-flush RESET + return: [A RESET(frees), B, A] — A's final
        # request must see a fresh bucket (store remove lands at flush
        # end), not resurrect pre-flush state.
        clock["now"] += 5
        got = eng.check_batch(
            [mk(a, 1, int(Behavior.RESET_REMAINING)), mk(b, 1), mk(a, 1)]
        )
        want = [
            oracle.decide(mk(a, 1, int(Behavior.RESET_REMAINING)), clock["now"]),
            oracle.decide(mk(b, 1), clock["now"]),
            oracle.decide(mk(a, 1), clock["now"]),
        ]
        assert [g.remaining for g in got] == [w.remaining for w in want]
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# merge_snapshots_lww order-independence (standby/handover convergence)


@pytest.mark.parametrize("seed", [3, 11, 42])
def test_merge_snapshots_lww_shuffle_fuzz(seed):
    """The LWW merge rule (newer stamp wins; equal stamps -> the
    more-consumed side wins) must converge to ONE final table state no
    matter what order duplicate snapshots arrive in — standby promotion,
    anti-entropy repair, and handover echoes all replay overlapping row
    sets, so order-dependence would make recovery nondeterministic."""
    from gubernator_tpu.store.store import (
        ItemSnapshot,
        merge_snapshots_lww,
        snapshots_from_engine,
    )

    rng = random.Random(seed)
    keys = [f"lww{i}" for i in range(10)]
    snaps = []
    for _ in range(60):
        k = rng.choice(keys)
        stamp = NOW + rng.choice([0, 0, 1000, 2000])  # many stamp ties
        snaps.append(
            ItemSnapshot(
                key=k, algorithm=int(Algorithm.TOKEN_BUCKET), limit=100,
                duration=600_000, remaining=rng.randrange(0, 101),
                stamp=stamp, expire_at=stamp + 600_000,
            )
        )

    # The expected winner per key, computed independently of the merge:
    # max by (stamp, consumed) == (stamp, -remaining).
    want = {}
    for s in snaps:
        cur = want.get(s.key)
        if cur is None or (s.stamp, -s.remaining) > (cur.stamp, -cur.remaining):
            want[s.key] = s

    states = []
    for trial in range(3):
        order = snaps[:]
        rng.shuffle(order)
        eng = DeviceEngine(
            EngineConfig(num_groups=1 << 9, batch_size=32),
            now_fn=lambda: NOW,
        )
        try:
            # Split into random merge batches too (chunked ships).
            i = 0
            while i < len(order):
                n = rng.randrange(1, 9)
                merge_snapshots_lww(eng, order[i : i + n])
                i += n
            state = {
                s.key: (s.stamp, s.remaining)
                for s in snapshots_from_engine(eng)
            }
        finally:
            eng.close()
        states.append(state)

    assert states[0] == states[1] == states[2]
    assert states[0] == {
        k: (s.stamp, s.remaining) for k, s in want.items()
    }
