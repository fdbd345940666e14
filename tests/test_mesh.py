"""Multi-device sharded execution on the virtual 8-device CPU mesh:
owner-sharded decide parity with the oracle, and the ICI GLOBAL
replica/sync consistency contract."""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq, Status
from gubernator_tpu.models.oracle import OracleEngine
from gubernator_tpu.ops.encode import encode_batch
from gubernator_tpu.ops.layout import batch_entry
from gubernator_tpu.parallel import ici
from gubernator_tpu.parallel import mesh as pmesh

NOW = 1_753_700_000_000
NDEV = 8


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices()
    assert len(devices) >= NDEV
    return pmesh.make_mesh(devices[:NDEV])


def mk(key, hits=1, **kw):
    kw.setdefault("duration", 60_000)
    kw.setdefault("limit", 10)
    return RateLimitReq(name="m", unique_key=key, hits=hits, **kw)


def test_sharded_decide_matches_oracle(mesh):
    num_groups = 8 * NDEV
    table = pmesh.create_sharded_table(mesh, num_groups, ways=8)
    decide_fn = batch_entry(
        pmesh.make_sharded_decide(mesh, num_groups, ways=8)
    )

    oracle = OracleEngine()
    reqs = [
        mk(f"k{i}", hits=i % 4, algorithm=Algorithm.LEAKY_BUCKET if i % 2 else Algorithm.TOKEN_BUCKET)
        for i in range(24)
    ]
    # distinct groups within the batch (assembler invariant)
    from gubernator_tpu.api.keys import group_of, key_hash128

    seen = set()
    uniq = []
    for r in reqs:
        g = group_of(key_hash128(r.hash_key())[1], num_groups)
        if g not in seen:
            seen.add(g)
            uniq.append(r)

    b = encode_batch([dataclasses.replace(r) for r in uniq], NOW, num_groups, 32)
    table, out = decide_fn(table, b, NOW)
    for i, r in enumerate(uniq):
        want = oracle.decide(dataclasses.replace(r), NOW)
        got = (int(out.status[i]), int(out.limit[i]), int(out.remaining[i]), int(out.reset_time[i]))
        assert got == (want.status, want.limit, want.remaining, want.reset_time), r

    # Second pass: state persists on the owning shards
    b2 = encode_batch([dataclasses.replace(r) for r in uniq], NOW + 5, num_groups, 32)
    table, out2 = decide_fn(table, b2, NOW + 5)
    for i, r in enumerate(uniq):
        want = oracle.decide(dataclasses.replace(r), NOW + 5)
        assert int(out2.remaining[i]) == want.remaining, r
    assert int(out2.hits) == len(uniq)


def _global_req(key, hits, limit=1000):
    return mk(key, hits=hits, limit=limit, behavior=Behavior.GLOBAL)


def test_ici_replica_answers_locally_and_converges(mesh):
    num_slots = 64 * NDEV
    state = ici.create_ici_state(mesh, num_slots)
    replica_fn = batch_entry(ici.make_replica_decide(mesh, num_slots))
    sync_fn = ici.make_sync_step(mesh, num_slots)

    # One key, hit from replica (home=3). home != owner for determinism:
    # find the key's slot owner and pick a different home.
    from gubernator_tpu.api.keys import group_of, key_hash128

    key = "account:ici1"
    slot = group_of(key_hash128("m_" + key)[1], num_slots)
    owner_dev = slot // (num_slots // NDEV)
    home_dev = (owner_dev + 3) % NDEV

    b = encode_batch([_global_req(key, 10)], NOW, num_slots, 4)
    home = np.full((4,), home_dev, dtype=np.int64)
    state, out = replica_fn(state, b, home, NOW)
    assert (int(out.status[0]), int(out.remaining[0])) == (Status.UNDER_LIMIT, 990)

    # Before sync: other replicas (including the owner) know nothing —
    # a read from another home sees a fresh bucket.
    b0 = encode_batch([_global_req(key, 0)], NOW + 1, num_slots, 4)
    other = np.full((4,), (home_dev + 1) % NDEV, dtype=np.int64)
    state, out0 = replica_fn(state, b0, other, NOW + 1)
    assert int(out0.remaining[0]) == 1000

    # Sync tick: deltas psum to the owner, authoritative state rebroadcast.
    state, _diag = sync_fn(state, NOW + 2)

    # After sync every replica agrees.
    for d in range(NDEV):
        bq = encode_batch([_global_req(key, 0)], NOW + 3 + d, num_slots, 4)
        hm = np.full((4,), d, dtype=np.int64)
        state, outq = replica_fn(state, bq, hm, NOW + 3 + d)
        assert int(outq.remaining[0]) == 990, f"device {d} did not converge"


def test_ici_hits_from_many_replicas_sum_at_owner(mesh):
    num_slots = 64 * NDEV
    state = ici.create_ici_state(mesh, num_slots)
    replica_fn = batch_entry(ici.make_replica_decide(mesh, num_slots))
    sync_fn = ici.make_sync_step(mesh, num_slots)

    key = "account:ici-multi"
    # Every device hits its own replica with 5
    for d in range(NDEV):
        b = encode_batch([_global_req(key, 5)], NOW + d, num_slots, 4)
        state, _ = replica_fn(state, b, np.full((4,), d, dtype=np.int64), NOW + d)

    state, _diag = sync_fn(state, NOW + 100)

    b = encode_batch([_global_req(key, 0)], NOW + 200, num_slots, 4)
    state, out = replica_fn(state, b, np.zeros((4,), np.int64), NOW + 200)
    # Owner's own hits applied authoritatively + (NDEV-1) replicas' deltas
    assert int(out.remaining[0]) == 1000 - 5 * NDEV


def test_ici_over_limit_drains(mesh):
    num_slots = 64 * NDEV
    state = ici.create_ici_state(mesh, num_slots)
    replica_fn = batch_entry(ici.make_replica_decide(mesh, num_slots))
    sync_fn = ici.make_sync_step(mesh, num_slots)

    key = "account:ici-drain"
    from gubernator_tpu.api.keys import group_of, key_hash128

    slot = group_of(key_hash128("m_" + key)[1], num_slots)
    owner_dev = slot // (num_slots // NDEV)
    h1 = (owner_dev + 1) % NDEV
    h2 = (owner_dev + 2) % NDEV

    # Two replicas each consume most of the limit locally: combined they
    # overshoot. After sync the owner drains to zero (never negative).
    b1 = encode_batch([_global_req(key, 700)], NOW, num_slots, 4)
    state, o1 = replica_fn(state, b1, np.full((4,), h1, np.int64), NOW)
    assert int(o1.remaining[0]) == 300
    b2 = encode_batch([_global_req(key, 700)], NOW + 1, num_slots, 4)
    state, o2 = replica_fn(state, b2, np.full((4,), h2, np.int64), NOW + 1)
    assert int(o2.remaining[0]) == 300  # its own replica also saw only 700

    state, _diag = sync_fn(state, NOW + 10)

    b3 = encode_batch([_global_req(key, 0)], NOW + 20, num_slots, 4)
    state, o3 = replica_fn(state, b3, np.full((4,), owner_dev, np.int64), NOW + 20)
    assert int(o3.remaining[0]) == 0


def test_ici_eviction_drops_stale_pending(mesh):
    """A direct-mapped eviction between hit and sync must not credit the
    old key's pending hits to the new key."""
    from gubernator_tpu.api.keys import group_of, key_hash128

    num_slots = 8 * NDEV  # tiny table to find collisions quickly
    state = ici.create_ici_state(mesh, num_slots)
    replica_fn = batch_entry(ici.make_replica_decide(mesh, num_slots))
    sync_fn = ici.make_sync_step(mesh, num_slots)

    # find two distinct keys colliding at one slot
    by_slot = {}
    pair = None
    for i in range(10_000):
        k = f"collide:{i}"
        s = group_of(key_hash128("m_" + k)[1], num_slots)
        if s in by_slot and by_slot[s] != k:
            pair = (by_slot[s], k, s)
            break
        by_slot[s] = k
    assert pair, "no collision found"
    key_a, key_b, slot = pair
    owner_dev = slot // (num_slots // NDEV)
    home = (owner_dev + 1) % NDEV
    hm = np.full((4,), home, dtype=np.int64)

    # A pends 10 hits on a non-owner, then B evicts A before the sync.
    ba = encode_batch([_global_req(key_a, 10)], NOW, num_slots, 4)
    state, _ = replica_fn(state, ba, hm, NOW)
    bb = encode_batch([_global_req(key_b, 3)], NOW + 1, num_slots, 4)
    state, _ = replica_fn(state, bb, hm, NOW + 1)

    state, _diag = sync_fn(state, NOW + 10)

    # B's counter reflects only B's hits; A's hits were dropped with its
    # evicted entry (documented direct-mapped trade-off), never credited
    # to B.
    bq = encode_batch([_global_req(key_b, 0)], NOW + 20, num_slots, 4)
    state, out = replica_fn(state, bq, np.full((4,), owner_dev, np.int64), NOW + 20)
    assert int(out.remaining[0]) == 1000 - 3


def test_graft_entry_single_chip():
    import __graft_entry__ as ge
    import jax

    fn, args = ge.entry()
    table, out = jax.jit(fn)(*args)
    assert int(out.misses) > 0


def test_replica_steps_queue_what_the_owner_is_owed(mesh):
    """A run of replica launches, one a wave as the engine launches the
    replica tier: every lane is answered from its home device's replica
    alone, and exactly the hits taken at a replica that does not own the
    key wait in that replica's pending deltas for the next tick."""
    num_slots, ways, S = 64 * NDEV, 4, 5
    num_groups = num_slots // ways
    groups_per = num_groups // NDEV
    state = ici.create_ici_state(mesh, num_slots, ways)
    step_fn = batch_entry(ici.make_replica_decide(mesh, num_slots, ways))

    owed = np.zeros(NDEV, dtype=np.int64)
    for s in range(S):
        home_dev, hits = s % NDEV, 2 + s
        b = encode_batch(
            [_global_req(f"scan:{s}:{i}", hits=hits) for i in range(3)],
            NOW + s, num_groups, 8,
        )
        state, out = step_fn(
            state, b, np.full((8,), home_dev, dtype=np.int64), NOW + s
        )
        for lane in range(3):
            assert int(out.status[lane]) == Status.UNDER_LIMIT
            assert int(out.remaining[lane]) == 1000 - hits
            if int(b.group[lane]) // groups_per != home_dev:
                owed[home_dev] += hits
        assert not np.asarray(out.limit)[3:].any()  # padding lanes

    pending = np.asarray(state.pending).astype(np.int64)  # (dev, 2, slots)
    lo, hi = pending[:, 0], pending[:, 1]
    assert not hi.any()
    np.testing.assert_array_equal(lo.sum(axis=1), owed)
    assert owed.sum() > 0  # some key was decided away from its owner


def test_graft_entry_dryrun():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)
