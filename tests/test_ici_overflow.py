"""ICI replica-tier overflow: drift bounds + observability (VERDICT r3
item 5).

The contract being protected is cross-peer agreement on `remaining`
(reference functional_test.go:1815-1821). A W-way replica table adds a
failure mode the reference's unbounded owner cache lacks: when an owner
group's ways fill, late keys degrade to per-replica counting until
capacity frees. These tests pin the three regimes documented in
docs/architecture.md ("Overflow and drift bounds"):

  A. Sized correctly (live keys per group <= W): zero overflow, transient
     over-admission bounded by R x limit (R = replicas serving the key
     before the first rebroadcast lands), exact convergence after sync.
  B. Transient pressure: an overflow key is RETAINED replica-local with
     its counter and pending (kept > 0, drops == 0), and is adopted into
     the authoritative layout within one further tick once a way frees —
     no counter loss at any point.
  C. Capacity exhaustion (hot keys per group > W): drops occur (visible
     via the gauge); over-admission is bounded by limit per fresh
     re-insertion, and re-insertions are observable as cache misses —
     the same degradation shape as the reference's LRU cache evicting
     unexpired buckets under pressure (cache.go), which it surfaces via
     guber_unexpired_evictions; we surface ours via
     gubernator_global_overflow_{keys,drops_count}.
"""

import dataclasses

import numpy as np
import pytest

import jax

from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.ops.encode import encode_batch
from gubernator_tpu.ops.layout import batch_entry
from gubernator_tpu.parallel import ici
from gubernator_tpu.parallel import mesh as pmesh

NOW = 1_753_700_000_000
NDEV = 4


def _mesh():
    return pmesh.make_mesh(jax.devices()[:NDEV])


def _one(key: str, group: int, num_groups: int, now: int, *, hits=1, limit=10,
         duration=600_000):
    req = RateLimitReq(
        name="ovf", unique_key=key, algorithm=Algorithm.TOKEN_BUCKET,
        behavior=Behavior.GLOBAL, duration=duration, limit=limit, hits=hits,
    )
    b = encode_batch([dataclasses.replace(req)], now, num_groups, 2)
    b.group[0] = group  # pin the group: force way-collisions deterministically
    return b


class _Driver:
    def __init__(self, num_slots: int, ways: int):
        self.num_groups = num_slots // ways
        self.mesh = _mesh()
        self.state = ici.create_ici_state(self.mesh, num_slots, ways)
        self.decide = batch_entry(
            ici.make_replica_decide(self.mesh, num_slots, ways)
        )
        self.sync = ici.make_sync_step(self.mesh, num_slots, ways)
        self.kept = self.dropped = 0

    def hit(self, key, group, home, now, **kw):
        b = _one(key, group, self.num_groups, now, **kw)
        hm = np.full((2,), home, dtype=np.int64)
        self.state, out = self.decide(self.state, b, hm, now)
        return (
            int(out.status[0]),
            int(out.remaining[0]),
            int(out.misses),
        )

    def tick(self, now):
        self.state, diag = self.sync(self.state, now)
        d = np.asarray(diag)
        self.kept = int(d[:, 0].sum())
        self.dropped += int(d[:, 1].sum())
        return self.kept


def test_regime_a_bound_and_convergence():
    """<= W live keys per group: no overflow ever; over-admission <= R x
    limit; all replicas converge to max(0, limit - total_hits)."""
    drv = _Driver(num_slots=8, ways=2)  # 4 groups, groups_per=1
    group, owner = 2, 2
    homes = [0, 1, 3]  # R = 3 non-owner replicas
    limit = 10
    admitted = {k: 0 for k in ("a", "b")}
    sent = {k: 0 for k in ("a", "b")}
    now = NOW
    for i in range(30):
        for key in ("a", "b"):
            st, _rem, _miss = drv.hit(
                key, group, homes[i % 3], now + i, limit=limit
            )
            sent[key] += 1
            if st == 0:
                admitted[key] += 1
        if i % 7 == 6:
            drv.tick(now + i)
            assert drv.kept == 0 and drv.dropped == 0
    drv.tick(now + 1000)
    assert drv.kept == 0 and drv.dropped == 0
    for key in ("a", "b"):
        # every replica admits at most `limit` before the first
        # rebroadcast reaches it; syncs only tighten this
        assert limit <= admitted[key] <= len(homes) * limit, admitted
        # convergence: pending carried EVERY sent hit to the owner
        # (drain semantics floor at 0), rebroadcast made it uniform
        want = max(0, limit - sent[key])
        rems = set()
        for d in range(NDEV):
            _st, rem, _m = drv.hit(key, group, d, now + 2000, hits=0)
            rems.add(rem)
        assert rems == {want}, (key, rems, want)


def test_regime_b_retention_then_adoption():
    """An overflow key whose group has a free way is kept replica-local
    (counter + pending intact) and becomes authoritative next tick."""
    drv = _Driver(num_slots=16, ways=4)  # 4 groups x 4 ways
    group, owner = 1, 1
    limit = 10
    # k1 lands on the owner replica: authoritative immediately.
    drv.hit("k1", group, owner, NOW, hits=3, limit=limit)
    # k2 and k3 land at way0 of non-owner replicas; candidate selection
    # is per slot position with lowest-device-wins, so k2 (dev 2) shadows
    # k3 (dev 3) this tick.
    drv.hit("k2", group, 2, NOW, hits=3, limit=limit)
    drv.hit("k3", group, 3, NOW, hits=3, limit=limit)

    drv.tick(NOW + 10)
    # k3 survived replica-local: kept, nothing dropped
    assert drv.kept == 1 and drv.dropped == 0
    # its counter survived with it (remaining = 7 on its home replica)
    _st, rem, miss = drv.hit("k3", group, 3, NOW + 20, hits=0)
    assert rem == 7 and miss == 0

    drv.tick(NOW + 30)
    assert drv.kept == 0 and drv.dropped == 0  # adopted this tick
    # all three keys now authoritative and identical on EVERY replica
    for key in ("k1", "k2", "k3"):
        rems = {
            drv.hit(key, group, d, NOW + 40, hits=0)[1] for d in range(NDEV)
        }
        assert rems == {7}, (key, rems)


def test_regime_c_drops_observable_and_bounded():
    """Hot keys per group > W: drops happen and are counted; per-key
    over-admission is bounded by limit x (fresh insertions), with fresh
    insertions observable as cache misses."""
    drv = _Driver(num_slots=8, ways=2)  # 4 groups x 2 ways
    group = 0
    keys = [f"hot{i}" for i in range(6)]  # 6 keys >> 2 ways
    limit = 5
    admitted = {k: 0 for k in keys}
    misses = {k: 0 for k in keys}
    now = NOW
    for i in range(90):
        key = keys[i % len(keys)]
        home = 1 + (i % 3)  # non-owner replicas
        st, _rem, miss = drv.hit(key, group, home, now + i, limit=limit)
        misses[key] += miss
        if st == 0:
            admitted[key] += 1
        if i % 10 == 9:
            drv.tick(now + i)
    # the degraded regime is observable
    assert drv.dropped > 0
    # drift bound: each fresh insertion grants at most `limit` admissions
    for key in keys:
        assert admitted[key] <= limit * max(misses[key], 1), (
            key, admitted[key], misses[key]
        )


def test_engine_overflow_gauges():
    """IciEngine surfaces the overflow diagnostics through /metrics."""
    from gubernator_tpu.metrics import Metrics, engine_sync
    from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig

    eng = IciEngine(
        IciEngineConfig(
            num_groups=64, ways=2, num_slots=32, replica_ways=4,
            batch_size=128, sync_wait_s=3600.0,  # tick manually
        )
    )
    try:
        reqs = [
            RateLimitReq(
                name="ovf", unique_key=f"g{i}", behavior=Behavior.GLOBAL,
                duration=600_000, limit=100, hits=1,
            )
            for i in range(100)  # 100 keys >> 32 replica slots
        ]
        for f in [eng.check_async(r) for r in reqs]:
            f.result(timeout=30)
        eng.sync_now()
        # another wave after the merge saturates groups -> keeps or drops
        for f in [eng.check_async(r) for r in reqs]:
            f.result(timeout=30)
        eng.sync_now()
        assert eng.overflow_keys > 0 or eng.overflow_drops > 0
        m = Metrics()
        m.add_sync(engine_sync(eng))
        text = m.render().decode()
        assert "gubernator_global_overflow_keys" in text
        assert "gubernator_global_overflow_drops_count" in text
        assert "gubernator_global_sync_backlog" in text
    finally:
        eng.close()


def test_engine_sync_backlog_gauge():
    """With a 1-group-per-tick cap, a multi-group burst leaves a backlog
    the engine must surface through the gauge, and the backlog drains to
    zero over subsequent ticks."""
    from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig

    eng = IciEngine(
        IciEngineConfig(
            num_groups=64, ways=2, num_slots=32, replica_ways=4,
            batch_size=128, sync_wait_s=3600.0,  # tick manually
            max_sync_groups=1,
        )
    )
    try:
        # few keys: spread over >1 of the 8 groups WITHOUT exceeding any
        # group's 4 ways (a permanently overflow-retained group stays
        # active by design and would hold the backlog above zero)
        reqs = [
            RateLimitReq(
                name="bkl", unique_key=f"b{i}", behavior=Behavior.GLOBAL,
                duration=600_000, limit=100, hits=1,
            )
            for i in range(8)
        ]
        for f in [eng.check_async(r) for r in reqs]:
            f.result(timeout=30)
        eng.sync_now()
        assert eng.sync_backlog > 0, eng.sync_backlog
        for _ in range(16):
            eng.sync_now()
            if eng.sync_backlog == 0:
                break
        assert eng.sync_backlog == 0
    finally:
        eng.close()
