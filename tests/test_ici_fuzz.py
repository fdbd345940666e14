"""Differential fuzz of the ICI GLOBAL collective against an independent
Python model of its spec (replica decide + pending deltas + sync merge:
owner apply, cross-way key-checked delta summing, rank-packed adoption
into empty owner ways, replica-local retention of overflow entries,
rebroadcast, eviction pending-drop). Small tables force way-group
collisions; random time advances force expiry paths.

Runs at ways=1 (the degenerate per-slot geometry) AND ways=4 (the
production replica geometry, where a key sits in different ways on
different devices and the merge must key-match across ways).
"""

import copy
import dataclasses
import functools
import random

import numpy as np
import pytest

from gubernator_tpu.api.keys import group_of, key_hash128
from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq, Status
from gubernator_tpu.models.oracle import OracleEngine
from gubernator_tpu.ops.encode import encode_batch
from gubernator_tpu.ops.layout import batch_entry
from gubernator_tpu.parallel import ici
from gubernator_tpu.parallel import mesh as pmesh

import jax

NOW = 1_753_700_000_000
NDEV = 4


class IciModel:
    """Spec model: one OracleEngine per device (replica semantics) plus a
    per-device slot-occupancy map (W-way set-associative placement with
    decide's insertion priority: matched-expired > empty > expired >
    LRU, lowest way on ties) and per-device pending deltas recorded at
    the key's slot on that device. Sync implements the documented merge."""

    def __init__(self, num_slots: int, ways: int, ndev: int = NDEV):
        self.num_slots = num_slots
        self.ways = ways
        self.ndev = ndev
        self.num_groups = num_slots // ways
        self.groups_per = self.num_groups // ndev
        self.oracles = [OracleEngine() for _ in range(ndev)]
        # device -> slot -> hash_key occupying it
        self.slot_key = [dict() for _ in range(ndev)]
        self.pending = [dict() for _ in range(ndev)]  # slot -> hits
        self.lru = [dict() for _ in range(ndev)]  # slot -> last-touch ms

    # -- shared helpers ------------------------------------------------------

    def _live(self, dev: int, slot: int, now: int):
        """(key, item) when the slot holds a live (unexpired) entry."""
        k = self.slot_key[dev].get(slot)
        if k is None:
            return None
        item = self.oracles[dev].cache.get(k)
        if item is None or item.expire_at < now:
            return None
        return k, item

    def _choose_slot(self, dev: int, key: str, now: int) -> int:
        """decide's way choice (ops/decide.py _choose_slot)."""
        g = group_of(key_hash128(key)[1], self.num_groups)
        slots = [g * self.ways + w for w in range(self.ways)]
        # live match wins
        for s in slots:
            k = self.slot_key[dev].get(s)
            if k != key:
                continue
            item = self.oracles[dev].cache.get(k)
            if item is not None and item.expire_at >= now:
                return s
        # insertion priority: matched-expired > empty > expired > LRU
        best = None
        for w, s in enumerate(slots):
            k = self.slot_key[dev].get(s)
            item = self.oracles[dev].cache.get(k) if k is not None else None
            used = k is not None and item is not None
            expired = used and item.expire_at < now
            if used and k == key and expired:
                cat, tie = 0, w
            elif not used:
                cat, tie = 1, w
            elif expired:
                cat, tie = 2, w
            else:
                cat, tie = 3, self.lru[dev].get(s, 0)
            score = (cat, tie, w)
            if best is None or score < best[0]:
                best = (score, s)
        return best[1]

    # -- replica decide ------------------------------------------------------

    def decide(self, req: RateLimitReq, home: int, now: int):
        key = req.hash_key()
        slot = self._choose_slot(home, key, now)
        ora = self.oracles[home]
        prev = self.slot_key[home].get(slot)
        if prev is not None and prev != key:
            # W-way eviction: drop the old entry and its un-synced
            # pending deltas
            ora.cache.pop(prev, None)
            self.pending[home].pop(slot, None)
        self.slot_key[home][slot] = key
        self.lru[home][slot] = now
        resp = ora.decide(dataclasses.replace(req, metadata={}), now)
        g = slot // self.ways
        owned = g // self.groups_per == home
        # only what this replica took is queued for the owner (a
        # DRAIN_OVER_LIMIT request drained this copy and is relayed)
        took = resp.status == Status.UNDER_LIMIT or (
            req.behavior & Behavior.DRAIN_OVER_LIMIT
        )
        if not owned and req.hits != 0 and took:
            self.pending[home][slot] = self.pending[home].get(slot, 0) + req.hits
        return resp

    # -- sync ----------------------------------------------------------------

    def _crossway_inc(self, g: int, key: str, now: int) -> int:
        inc = 0
        for d in range(self.ndev):
            for w in range(self.ways):
                s = g * self.ways + w
                lv = self._live(d, s, now)
                if lv is not None and lv[0] == key:
                    inc += self.pending[d].get(s, 0)
        return inc

    def sync(self, now: int):
        from gubernator_tpu.models.bucket import FIXED_SHIFT

        W = self.ways

        def apply_inc(item, inc):
            item = copy.deepcopy(item)
            if inc != 0:
                st = item.value
                if item.algorithm == Algorithm.LEAKY_BUCKET:
                    st.remaining_s = max(st.remaining_s - (inc << FIXED_SHIFT), 0)
                else:
                    st.remaining = max(st.remaining - inc, 0)
            return item

        # merged[g]: way -> (key, item, lru) — the authoritative layout
        merged = {}
        for g in range(self.num_groups):
            owner_dev = g // self.groups_per
            slots = [g * W + w for w in range(W)]
            owner_live = {
                w: self._live(owner_dev, s, now) for w, s in enumerate(slots)
            }
            owner_keys = {lv[0] for lv in owner_live.values() if lv is not None}

            # candidates per slot position: lowest device with a live
            # entry whose key the owner layout lacks (zero-pending
            # entries are candidates too — read-created buckets must
            # reach the owner layout and converge; owner-known keys are
            # excluded at candidacy so a rebroadcast copy never shadows
            # a genuinely-missing key at the same position)
            cands = []  # (src_way, sel_dev, key, item)
            for w, s in enumerate(slots):
                for d in range(self.ndev):
                    lv = self._live(d, s, now)
                    if lv is not None and lv[0] not in owner_keys:
                        cands.append((w, d, lv[0], lv[1]))
                        break
            # dedup among candidates (lowest way wins)
            seen, uniq = set(), []
            for c in cands:
                if c[2] not in seen:
                    seen.add(c[2])
                    uniq.append(c)
            empties = [w for w in range(W) if owner_live[w] is None]

            mg = {}
            for w in range(W):
                lv = owner_live[w]
                if lv is None:
                    continue
                okey, oitem = lv
                inc = self._crossway_inc(g, okey, now)
                mg[w] = (okey, apply_inc(oitem, inc),
                         self.lru[owner_dev].get(slots[w], 0))
            for dst, (src_w, sel_d, akey, aitem) in zip(empties, uniq):
                src_slot = g * W + src_w
                inc = self._crossway_inc(g, akey, now) - self.pending[sel_d].get(
                    src_slot, 0
                )
                mg[dst] = (akey, apply_inc(aitem, inc),
                           self.lru[sel_d].get(src_slot, 0))
            merged[g] = mg

        # rebroadcast + replica-local retention: merged layout lands
        # identically on every device; local overflow survivors relocate
        # into merged-free ways in rank order (pending and lru ride
        # along); survivors beyond the group's free capacity drop.
        for d in range(self.ndev):
            new_sk, new_pend, new_lru, new_cache = {}, {}, {}, {}
            for g in range(self.num_groups):
                mg = merged[g]
                merged_keys = {e[0] for e in mg.values()}
                for w, (k, item, lru) in mg.items():
                    s = g * W + w
                    new_sk[s] = k
                    new_cache[k] = copy.deepcopy(item)
                    new_lru[s] = lru
                free = [w for w in range(W) if w not in mg]
                surv = []
                for w in range(W):
                    s = g * W + w
                    lv = self._live(d, s, now)
                    if lv is not None and lv[0] not in merged_keys:
                        surv.append((s, lv))
                for dst_w, (src_s, (k, item)) in zip(free, surv):
                    s = g * W + dst_w
                    new_sk[s] = k
                    new_cache[k] = item  # device's own item, unchanged
                    new_lru[s] = self.lru[d].get(src_s, 0)
                    if src_s in self.pending[d]:
                        new_pend[s] = self.pending[d][src_s]
            self.slot_key[d] = new_sk
            self.pending[d] = new_pend
            self.lru[d] = new_lru
            self.oracles[d].cache = new_cache


def _run_fuzz(seed: int, num_slots: int, ways: int, layout: str = "fused"):
    mesh = pmesh.make_mesh(jax.devices()[:NDEV])
    num_groups = num_slots // ways
    state = ici.create_ici_state(mesh, num_slots, ways, layout=layout)
    replica_fn = batch_entry(
        ici.make_replica_decide(mesh, num_slots, ways, layout=layout)
    )
    sync_fn = ici.make_sync_step(mesh, num_slots, ways, layout=layout)
    model = IciModel(num_slots, ways)

    rng = random.Random(seed)
    keys = [f"fz:{i}" for i in range(20)]  # 20 keys: group collisions
    now = NOW

    for step in range(250):
        r = rng.random()
        if r < 0.75:
            key = rng.choice(keys)
            home = rng.randrange(NDEV)
            req = RateLimitReq(
                name="z",
                unique_key=key,
                algorithm=rng.choice(
                    [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                ),
                behavior=Behavior.GLOBAL,
                duration=rng.choice([500, 5_000, 60_000]),
                limit=rng.choice([3, 10, 100]),
                hits=rng.choice([-2, 0, 1, 1, 2, 5, 50]),
            )
            b = encode_batch([dataclasses.replace(req)], now, num_groups, 2)
            hm = np.full((2,), home, dtype=np.int64)
            state, out = replica_fn(state, b, hm, now)
            want = model.decide(req, home, now)
            got = (int(out.status[0]), int(out.remaining[0]), int(out.reset_time[0]))
            assert got == (int(want.status), int(want.remaining), int(want.reset_time)), (
                f"seed {seed} step {step} key {key} home {home}: {got} != "
                f"{(int(want.status), int(want.remaining), int(want.reset_time))}"
            )
        elif r < 0.9:
            state, _diag = sync_fn(state, now)
            model.sync(now)
        else:
            now += rng.choice([1, 100, 1_000, 10_000])

    # final sync then full read-back comparison on every device
    state, _diag = sync_fn(state, now)
    model.sync(now)

    for key in keys:
        for d in range(NDEV):
            req = RateLimitReq(
                name="z", unique_key=key, behavior=Behavior.GLOBAL,
                duration=60_000, limit=100, hits=0,
            )
            b = encode_batch([dataclasses.replace(req)], now, num_groups, 2)
            hm = np.full((2,), d, dtype=np.int64)
            state, out = replica_fn(state, b, hm, now)
            want = model.decide(dataclasses.replace(req), d, now)
            got = (int(out.status[0]), int(out.remaining[0]))
            assert got == (int(want.status), int(want.remaining)), (
                f"seed {seed} final key {key} dev {d}"
            )


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_ici_sync_matches_model(seed):
    _run_fuzz(seed, num_slots=NDEV * 8, ways=1)


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_ici_sync_matches_model_4way(seed):
    _run_fuzz(seed, num_slots=NDEV * 8, ways=4)


def _table_arrays(state):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(state.table)] + [
        np.asarray(state.pending)
    ]


def _sync_fixpoint(sync_fn, state, now, max_ticks=64):
    """Tick until the state stops changing (and the backlog, if the
    sync reports one, is drained). Overflow-retained groups make a
    single tick non-idempotent BY DESIGN — retention then
    adoption-when-freed settle over a couple of ticks — so the
    meaningful comparison point between sync flavors is the fixpoint."""
    prev = None
    for _ in range(max_ticks):
        state, diag = sync_fn(state, now)
        cur = [a.tobytes() for a in _table_arrays(state)]
        if prev == cur and int(np.asarray(diag)[0, 2]) == 0:
            return state
        prev = cur
    raise AssertionError("sync never reached a fixpoint")


@functools.lru_cache(maxsize=None)
def _capped_and_full(num_slots: int, ways: int, cap: int):
    """(mesh, replica decide, uncapped sync, sync capped at `cap`) of one
    geometry, compiled once for all the cases that share it."""
    mesh = pmesh.make_mesh(jax.devices()[:NDEV])
    return (
        mesh,
        batch_entry(ici.make_replica_decide(mesh, num_slots, ways)),
        ici.make_sync_step(mesh, num_slots, ways),
        ici.make_sync_step(mesh, num_slots, ways, max_sync_groups=cap),
    )


def _assert_same_tables(state_a, state_b):
    for x, y in zip(_table_arrays(state_a), _table_arrays(state_b)):
        np.testing.assert_array_equal(x, y)


def _random_traffic_matches(seed, num_slots, ways, cap):
    mesh, replica_fn, sync_full, sync_cap = _capped_and_full(
        num_slots, ways, cap
    )
    num_groups = num_slots // ways
    state_a = ici.create_ici_state(mesh, num_slots, ways)
    state_b = ici.create_ici_state(mesh, num_slots, ways)

    rng = random.Random(seed)
    keys = [f"cf:{i}" for i in range(24)]
    now = NOW
    for step in range(120):
        r = rng.random()
        if r < 0.8:
            req = RateLimitReq(
                name="z",
                unique_key=rng.choice(keys),
                algorithm=rng.choice(
                    [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                ),
                behavior=Behavior.GLOBAL,
                duration=rng.choice([500, 60_000]),
                limit=rng.choice([3, 100]),
                hits=rng.choice([0, 1, 2, 5]),
            )
            b = encode_batch([dataclasses.replace(req)], now, num_groups, 2)
            hm = np.full((2,), rng.randrange(NDEV), dtype=np.int64)
            state_a, _ = replica_fn(state_a, b, hm, now)
            b2 = encode_batch([dataclasses.replace(req)], now, num_groups, 2)
            state_b, _ = replica_fn(state_b, b2, hm, now)
        elif r < 0.93:
            now += rng.choice([1, 1_000, 10_000])
        else:
            state_a = _sync_fixpoint(sync_full, state_a, now)
            state_b = _sync_fixpoint(sync_cap, state_b, now)
            _assert_same_tables(state_a, state_b)

    state_a = _sync_fixpoint(sync_full, state_a, now)
    state_b = _sync_fixpoint(sync_cap, state_b, now)
    _assert_same_tables(state_a, state_b)


def _planted_groups_match(active, num_slots, ways, cap):
    """One hit in each of `active` distinct groups, then the ticks: the
    first capped one merges what fits its cap at the least width of the
    ladder that holds it, and leaves the rest as its backlog."""
    mesh, replica_fn, sync_full, sync_cap = _capped_and_full(
        num_slots, ways, cap
    )
    num_groups = num_slots // ways
    state_a = ici.create_ici_state(mesh, num_slots, ways)
    state_b = ici.create_ici_state(mesh, num_slots, ways)
    seen = set()
    i = 0
    while len(seen) < active:
        i += 1
        req = RateLimitReq(
            name="z", unique_key=f"pl:{i}", behavior=Behavior.GLOBAL,
            duration=60_000, limit=100, hits=1,
        )
        group = group_of(key_hash128(req.hash_key())[1], num_groups)
        if group in seen:
            continue
        seen.add(group)
        hm = np.full((2,), i % NDEV, dtype=np.int64)
        b = encode_batch([dataclasses.replace(req)], NOW, num_groups, 2)
        state_a, _ = replica_fn(state_a, b, hm, NOW)
        b2 = encode_batch([dataclasses.replace(req)], NOW, num_groups, 2)
        state_b, _ = replica_fn(state_b, b2, hm, NOW)

    state_b, diag = sync_cap(state_b, NOW)
    _kept, _dropped, backlog, merged, width = (
        int(x) for x in np.asarray(diag)[0, :5]
    )
    assert merged == min(active, cap)
    assert backlog == active - merged
    assert width == min(w for w in ici._block_widths(cap) if w >= merged)

    state_a = _sync_fixpoint(sync_full, state_a, NOW)
    state_b = _sync_fixpoint(sync_cap, state_b, NOW)
    _assert_same_tables(state_a, state_b)


# `active` None: random GLOBAL traffic. A number: that many groups made
# active at once, below, on and above each width of the ladder (1, 8, 64
# of 256 groups; 1, 4, 32 of 64 four-way ones) and above the cap.
@pytest.mark.parametrize(
    "seed,ways,active",
    [(5, 1, None), (6, 4, None)]
    + [(7, 1, n) for n in (0, 1, 2, 7, 8, 9, 63, 64, 65, 100)]
    + [(8, 4, n) for n in (1, 3, 4, 5, 31, 32, 33, 40)],
)
def test_capped_sync_matches_full(seed, ways, active):
    """Delta-compacted sync (max_sync_groups=C) must reach the same
    fixpoint as the unbounded merge at the same timestamp — under
    random GLOBAL traffic including overflow/retention regimes, and
    whatever width of its ladder a tick merges at. The merge is
    group-local, so which tick a group is processed on, and in how wide
    a block, cannot change where it converges."""
    if active is None:
        _random_traffic_matches(seed, num_slots=NDEV * 8, ways=ways, cap=2)
    else:
        _planted_groups_match(
            active, num_slots=NDEV * 64, ways=ways, cap={1: 64, 4: 32}[ways]
        )


# The factories default to the fused layout (the two suites above), so
# wide keeps explicit differential coverage: both hot paths must remain
# bit-exact against the same spec model (VERDICT r4 item 2).
@pytest.mark.parametrize("seed,ways", [(1, 1), (2, 4)])
def test_ici_sync_matches_model_wide(seed, ways):
    _run_fuzz(seed, num_slots=NDEV * 8, ways=ways, layout="wide")


# Replica groups of 2 and of 8 ways: four groups share one fused line
# of a replica's table, or one group fills it, and the sync tick takes
# and puts whole lines either way (ops/fused.py take_groups/put_groups).
@pytest.mark.parametrize("seed,ways", [(3, 2), (4, 8)])
def test_ici_sync_matches_model_line_geometry(seed, ways):
    _run_fuzz(seed, num_slots=NDEV * 8, ways=ways, layout="fused")


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("seed", [31, 32, 33, 34])
def test_replica_decide_matches_oracle(seed, n_dev):
    """The replica decide (the program GLOBAL traffic launches) against
    the oracle, at the replica tier's 4 ways: a key always lands on the
    same replica here, so with no sync in between one oracle stands for
    them all. Waves of up to 16 lanes in distinct groups, the kernel
    fuzz's request mix with GLOBAL on most lanes."""
    ways, groups, lanes = 4, 256, 16
    mesh = pmesh.make_mesh(jax.devices()[:n_dev])
    state = ici.create_ici_state(mesh, groups * ways, ways)
    decide = batch_entry(ici.make_replica_decide(mesh, groups * ways, ways))
    oracle = OracleEngine()
    rng = random.Random(seed)
    keys = [f"rp:{i}" for i in range(40)]
    now = NOW
    for step in range(60):
        now += rng.choice([0, 1, 7, 500, 3000, 61_000])
        reqs, homes, used = [], [], set()
        for _ in range(rng.randrange(1, lanes + 1)):
            behavior = int(Behavior.GLOBAL) if rng.random() < 0.8 else 0
            if rng.random() < 0.08:
                behavior |= Behavior.RESET_REMAINING
            if rng.random() < 0.15:
                behavior |= Behavior.DRAIN_OVER_LIMIT
            r = RateLimitReq(
                name=rng.choice(["a", "b"]),
                unique_key=rng.choice(keys),
                algorithm=rng.choice(
                    [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                ),
                behavior=behavior,
                duration=rng.choice([0, 5, 1000, 30_000, 60_000]),
                limit=rng.choice([0, 1, 2, 10, 100, 2000]),
                hits=rng.choice([-5, -1, 0, 1, 1, 2, 5, 10, 99, 3000]),
                burst=rng.choice([0, 0, 5, 30]),
            )
            lo = key_hash128(r.hash_key())[1]
            g = group_of(lo, groups)
            if g not in used:
                used.add(g)
                reqs.append(r)
                homes.append(lo % n_dev)
        b = encode_batch(
            [dataclasses.replace(r) for r in reqs], now, groups, lanes
        )
        home = np.zeros(lanes, dtype=np.int64)
        home[: len(homes)] = homes
        state, out = decide(state, b, home, now)
        for i, r in enumerate(reqs):
            want = oracle.decide(dataclasses.replace(r), now)
            got = (int(out.status[i]), int(out.limit[i]),
                   int(out.remaining[i]), int(out.reset_time[i]))
            assert got == (int(want.status), int(want.limit),
                           int(want.remaining), int(want.reset_time)), (
                f"seed {seed} x{n_dev} step {step} lane {i}: {r}"
            )
    # A replica owes the owner what it took of keys it does not own:
    # one device owns everything, four leave deltas behind.
    owed = np.asarray(ici.pending_hits(state))
    assert owed.shape == (n_dev, groups * ways)
    assert bool(owed.any()) == (n_dev > 1)
