"""Columnar serving path differential tests: wire.parse_requests +
DeviceEngine.check_columns must produce byte-identical decisions to the
protobuf-object path for the same request stream (incl. in-batch
duplicate keys, whose per-key order the wave logic must preserve)."""

import dataclasses
import random

import numpy as np
import pytest

from gubernator_tpu import wire
from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig
from gubernator_tpu.service import pb

NOW = 1_753_700_000_000

pytestmark = pytest.mark.skipif(
    not wire.available(), reason="native wirepath unavailable"
)


def to_proto_bytes(reqs):
    msg = pb.pb.GetRateLimitsReq()
    for r in reqs:
        msg.requests.append(pb.req_to_pb(r))
    return msg.SerializeToString()


def mk_engine(clock):
    return DeviceEngine(
        EngineConfig(num_groups=1 << 8, batch_size=64, batch_wait_s=0.001),
        now_fn=lambda: clock["now"],
    )


@pytest.mark.parametrize("seed", [3, 4])
def test_columns_match_object_path(seed):
    rng = random.Random(seed)
    clock = {"now": NOW}
    eng_a = mk_engine(clock)  # columnar
    eng_b = mk_engine(clock)  # object path
    keys = [f"fp{i}" for i in range(10)]
    try:
        for step in range(60):
            if rng.random() < 0.2:
                clock["now"] += rng.choice([5, 700, 70_000])
            batch = []
            for _ in range(rng.randint(1, 40)):
                behavior = 0
                if rng.random() < 0.1:
                    behavior |= Behavior.RESET_REMAINING
                if rng.random() < 0.1:
                    behavior |= Behavior.DRAIN_OVER_LIMIT
                batch.append(
                    RateLimitReq(
                        name="fp",
                        unique_key=rng.choice(keys),
                        algorithm=rng.choice(
                            [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                        ),
                        behavior=behavior,
                        duration=rng.choice([100, 60_000]),
                        limit=rng.choice([3, 10, 50]),
                        hits=rng.choice([0, 1, 2, 5, 60]),
                        burst=rng.choice([0, 0, 7]),
                    )
                )
            cols = wire.parse_requests(to_proto_bytes(batch))
            assert cols is not None and cols.n == len(batch)
            got = eng_a.check_columns(cols, now=clock["now"])
            assert got is not None
            status, limit, remaining, reset_time = got
            want = eng_b.check_batch([dataclasses.replace(r) for r in batch])
            for i, w in enumerate(want):
                assert (
                    int(status[i]), int(limit[i]), int(remaining[i]),
                    int(reset_time[i]),
                ) == (int(w.status), w.limit, w.remaining, w.reset_time), (
                    f"seed {seed} step {step} item {i}: {batch[i]}"
                )
    finally:
        eng_a.close()
        eng_b.close()


@pytest.mark.parametrize("seed", [7, 8])
def test_columns_match_object_path_with_store(seed):
    """Store-attached equivalence: columnar and object paths must produce
    identical decisions AND identical persisted store state, including
    across evictions (read-through) and RESET_REMAINING (remove)."""
    from gubernator_tpu.store.store import MemoryStore, attach_store

    rng = random.Random(seed)
    clock = {"now": NOW}

    def mk(store):
        eng = DeviceEngine(
            EngineConfig(num_groups=1 << 3, ways=2, batch_size=64,
                         batch_wait_s=0.001),
            now_fn=lambda: clock["now"],
        )
        attach_store(eng, store)
        return eng

    store_a, store_b = MemoryStore(), MemoryStore()
    eng_a, eng_b = mk(store_a), mk(store_b)  # columnar vs object
    keys = [f"st{i}" for i in range(24)]  # 24 keys on 16 slots: churn
    try:
        for step in range(50):
            if rng.random() < 0.25:
                clock["now"] += rng.choice([5, 700, 70_000])
            batch = []
            for _ in range(rng.randint(1, 24)):
                behavior = 0
                if rng.random() < 0.12:
                    behavior |= Behavior.RESET_REMAINING
                if rng.random() < 0.1:
                    behavior |= Behavior.DRAIN_OVER_LIMIT
                batch.append(
                    RateLimitReq(
                        name="st", unique_key=rng.choice(keys),
                        algorithm=rng.choice(
                            [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                        ),
                        behavior=behavior,
                        duration=rng.choice([100, 60_000]),
                        limit=rng.choice([3, 10, 50]),
                        hits=rng.choice([0, 1, 2, 5, 60]),
                    )
                )
            cols = wire.parse_requests(to_proto_bytes(batch))
            got = eng_a.check_columns(cols, now=clock["now"])
            assert got is not None, f"store path fell back at step {step}"
            status, limit, remaining, reset_time = got
            want = eng_b.check_batch([dataclasses.replace(r) for r in batch])
            for i, w in enumerate(want):
                assert (
                    int(status[i]), int(limit[i]), int(remaining[i]),
                    int(reset_time[i]),
                ) == (int(w.status), w.limit, w.remaining, w.reset_time), (
                    f"seed {seed} step {step} item {i}: {batch[i]}"
                )
            assert store_a.data == store_b.data, (
                f"seed {seed} step {step}: persisted state diverged"
            )
    finally:
        eng_a.close()
        eng_b.close()


def test_columns_store_readthrough_after_restart():
    """A fresh engine (cold table) must recover counters from the store
    through the columnar path — the reference's read-through contract
    (algorithms.go:45-51)."""
    from gubernator_tpu.store.store import MemoryStore, attach_store

    clock = {"now": NOW}
    store = MemoryStore()

    def spawn():
        eng = DeviceEngine(
            EngineConfig(num_groups=1 << 6, batch_size=64, batch_wait_s=0.001),
            now_fn=lambda: clock["now"],
        )
        attach_store(eng, store)
        return eng

    reqs = [
        RateLimitReq(name="rt", unique_key="persist", duration=600_000,
                     limit=10, hits=3)
    ]
    eng = spawn()
    try:
        cols = wire.parse_requests(to_proto_bytes(reqs))
        _, _, remaining, _ = eng.check_columns(cols, now=clock["now"])
        assert int(remaining[0]) == 7
    finally:
        eng.close()
    # "restart": new engine, empty table, same store
    eng = spawn()
    try:
        cols = wire.parse_requests(to_proto_bytes(reqs))
        _, _, remaining, _ = eng.check_columns(cols, now=clock["now"])
        assert int(remaining[0]) == 4, "store state not recovered columnar"
        assert store.get_calls >= 1
    finally:
        eng.close()


def test_columns_store_write_behind_failure_never_raises():
    """A store backend raising from on_change/remove AFTER the table
    committed must not escape check_columns — the columnar caller treats
    an exception as 'retry via the object path', which would double-apply
    every committed hit. Durability degrades, serving does not."""
    from gubernator_tpu.store.store import MemoryStore, attach_store

    class FlakyStore(MemoryStore):
        def __init__(self):
            super().__init__()
            self.fail = False

        def on_change(self, items):
            if self.fail:
                raise RuntimeError("store outage")
            super().on_change(items)

    clock = {"now": NOW}
    store = FlakyStore()
    eng = DeviceEngine(
        EngineConfig(num_groups=1 << 6, batch_size=64, batch_wait_s=0.001),
        now_fn=lambda: clock["now"],
    )
    attach_store(eng, store)
    try:
        reqs = [
            RateLimitReq(name="fl", unique_key="k", duration=600_000,
                         limit=10, hits=2)
        ]
        cols = wire.parse_requests(to_proto_bytes(reqs))
        _, _, remaining, _ = eng.check_columns(cols, now=clock["now"])
        assert int(remaining[0]) == 8
        store.fail = True
        out = eng.check_columns(
            wire.parse_requests(to_proto_bytes(reqs)), now=clock["now"]
        )
        assert out is not None, "store outage must not kill the fast path"
        assert int(out[2][0]) == 6  # counter advanced exactly once
        store.fail = False
        out = eng.check_columns(
            wire.parse_requests(to_proto_bytes(reqs)), now=clock["now"]
        )
        assert int(out[2][0]) == 4
    finally:
        eng.close()


def test_columns_multibyte_name_store_key():
    """Multi-byte UTF-8 names: name_lens is a BYTE count; the store key
    must still be the exact name+'_'+unique_key split (a char-count split
    would persist under a wrong key and read-through would miss forever)."""
    from gubernator_tpu.store.store import MemoryStore, attach_store

    clock = {"now": NOW}
    store = MemoryStore()
    eng = DeviceEngine(
        EngineConfig(num_groups=1 << 6, batch_size=64, batch_wait_s=0.001),
        now_fn=lambda: clock["now"],
    )
    attach_store(eng, store)
    try:
        reqs = [
            RateLimitReq(name="café", unique_key="naïve_k", duration=600_000,
                         limit=10, hits=3)
        ]
        cols = wire.parse_requests(to_proto_bytes(reqs))
        _, _, remaining, _ = eng.check_columns(cols, now=clock["now"])
        assert int(remaining[0]) == 7
        assert "café_naïve_k" in store.data
    finally:
        eng.close()
    # read-through on a fresh engine finds it
    eng = DeviceEngine(
        EngineConfig(num_groups=1 << 6, batch_size=64, batch_wait_s=0.001),
        now_fn=lambda: clock["now"],
    )
    attach_store(eng, store)
    try:
        reqs = [
            RateLimitReq(name="café", unique_key="naïve_k", duration=600_000,
                         limit=10, hits=1)
        ]
        cols = wire.parse_requests(to_proto_bytes(reqs))
        _, _, remaining, _ = eng.check_columns(cols, now=clock["now"])
        assert int(remaining[0]) == 6
    finally:
        eng.close()


def test_columns_duplicate_key_sequencing():
    """Same key N times in one batch: strictly sequential consumption,
    and over-limit must not consume (the reference's serialized-worker
    contract)."""
    clock = {"now": NOW}
    eng = mk_engine(clock)
    try:
        reqs = [
            RateLimitReq(name="fp", unique_key="dup", duration=60_000,
                         limit=10, hits=4)
            for _ in range(4)
        ]
        cols = wire.parse_requests(to_proto_bytes(reqs))
        status, limit, remaining, _ = eng.check_columns(cols, now=clock["now"])
        assert list(remaining) == [6, 2, 2, 2]
        assert list(status) == [0, 0, 1, 1]
    finally:
        eng.close()


def test_columns_response_wire_bytes():
    """End-to-end bytes: parse -> decide -> build_responses must decode
    as a correct GetRateLimitsResp."""
    clock = {"now": NOW}
    eng = mk_engine(clock)
    try:
        reqs = [
            RateLimitReq(name="fp", unique_key=f"w{i}", duration=60_000,
                         limit=100, hits=i)
            for i in range(5)
        ]
        cols = wire.parse_requests(to_proto_bytes(reqs))
        status, limit, remaining, reset_time = eng.check_columns(
            cols, now=clock["now"]
        )
        raw = wire.build_responses(status, limit, remaining, reset_time)
        out = pb.pb.GetRateLimitsResp.FromString(raw)
        assert len(out.responses) == 5
        for i, r in enumerate(out.responses):
            assert r.remaining == 100 - i
            assert r.limit == 100
    finally:
        eng.close()


def test_local_mask_matches_get():
    """Vectorized ring ownership must place every key exactly like the
    scalar get() (bisect_left + wraparound)."""
    from gubernator_tpu.parallel.hash_ring import ReplicatedConsistentHash

    class P:
        def __init__(self, addr, own):
            class I:
                pass

            self.info = I()
            self.info.grpc_address = addr
            self.info.is_owner = own

    ring = ReplicatedConsistentHash()
    peers = [P(f"10.0.0.{i}:81", i == 2) for i in range(5)]
    for p in peers:
        ring.add(p)

    keys = [f"bench_mask_{i}" for i in range(2000)]
    import numpy as np

    offsets = np.zeros(len(keys) + 1, np.int64)
    data = b"".join(k.encode() for k in keys)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    from gubernator_tpu.service.fastpath import _RING_VARIANT

    hashes = wire.fnv1_batch(
        np.frombuffer(data, np.uint8).copy(), offsets,
        _RING_VARIANT[ring.hash_fn],
    )
    mask = ring.local_mask(hashes)
    for i, k in enumerate(keys):
        assert bool(mask[i]) == bool(ring.get(k).info.is_owner), k


def test_malformed_and_invalid_utf8_fall_back(loop_thread):
    """Adversarial wire bytes: huge length varints must not crash the
    daemon, and invalid-UTF-8 keys get the object path's INVALID_ARGUMENT
    instead of being silently served."""
    import grpc as grpc_mod

    from gubernator_tpu.service.config import DaemonConfig
    from gubernator_tpu.service.daemon import Daemon

    async def scenario():
        d = await Daemon.spawn(DaemonConfig(cache_size=1024))
        try:
            async with grpc_mod.aio.insecure_channel(d.grpc_address) as ch:
                call = ch.unary_unary("/pb.gubernator.V1/GetRateLimits")
                # huge length varint inside the message
                bad = bytes(
                    [0x0A, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF,
                     0xFF, 0x01, 0x01]
                )
                try:
                    await call(bad)
                    assert False, "malformed bytes accepted"
                except grpc_mod.aio.AioRpcError as e:
                    assert e.code() == grpc_mod.StatusCode.INVALID_ARGUMENT
                # invalid UTF-8 unique_key -> INVALID_ARGUMENT via fallback
                msg = pb.pb.GetRateLimitsReq()
                msg.requests.append(
                    pb.pb.RateLimitReq(
                        name="u", unique_key="marker", duration=60000,
                        limit=5, hits=1,
                    )
                )
                raw = bytearray(msg.SerializeToString())
                ix = bytes(raw).index(b"marker")
                raw[ix] = 0xFF
                try:
                    await call(bytes(raw))
                    assert False, "invalid utf-8 accepted"
                except grpc_mod.aio.AioRpcError as e:
                    assert e.code() == grpc_mod.StatusCode.INVALID_ARGUMENT
                # and the daemon still serves normal traffic
                ok_msg = pb.pb.GetRateLimitsReq()
                ok_msg.requests.append(
                    pb.pb.RateLimitReq(
                        name="u", unique_key="fine", duration=60000,
                        limit=5, hits=1,
                    )
                )
                out = pb.pb.GetRateLimitsResp.FromString(
                    await call(ok_msg.SerializeToString())
                )
                assert out.responses[0].remaining == 4
        finally:
            await d.close()

    loop_thread.run(scenario(), timeout=120)


def _tag(field: int, wt: int) -> bytes:
    assert field < 16
    return bytes([(field << 3) | wt])


def _varint(v: int) -> bytes:
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def test_wire_type_confusion_adversarial():
    """The exact shape from the round-2 security review: a scalar field
    (hits=3) encoded as wire-type 2 whose payload embeds a fake field-2
    length record. The count pass skips it by wire type; the parse pass
    must do the same — never reinterpret the payload as key bytes (that
    disagreement was a heap overflow into the count-sized key buffer)."""
    inner = b""
    inner += _tag(1, 2) + _varint(1) + b"n"
    inner += _tag(2, 2) + _varint(1) + b"k"
    fake = _tag(2, 2) + _varint(40) + b"x" * 40  # fake unique_key record
    inner += _tag(3, 2) + _varint(len(fake)) + fake
    data = _tag(1, 2) + _varint(len(inner)) + inner

    msg = pb.pb.GetRateLimitsReq.FromString(data)
    assert len(msg.requests) == 1
    assert msg.requests[0].hits == 0  # mis-typed field -> unknown, skipped

    cols = wire.parse_requests(data)
    assert cols is not None and cols.n == 1
    assert cols.key_string(0) == "n_k"
    assert int(cols.hits[0]) == 0
    # count and parse agree on key bytes (the overflow invariant)
    assert int(cols.key_offsets[-1]) == len(cols.key_data)


def test_invalid_field_numbers_rejected():
    """Field 0 and field numbers above 2^29-1 are DecodeErrors for the
    object path; the fast path must reject them too — a huge field
    number must never truncate onto name/unique_key and become key
    material."""
    def wrap(inner: bytes) -> bytes:
        return _tag(1, 2) + _varint(len(inner)) + inner

    base = _tag(1, 2) + _varint(1) + b"n" + _tag(2, 2) + _varint(1) + b"k"
    # field 0 tag inside an item
    assert wire.parse_requests(wrap(base + b"\x00")) is None
    # field 2^32 + 2 aliases to field 2 under 32-bit truncation
    huge = _varint(((1 << 32) + 2) << 3 | 2) + _varint(5) + b"alias"
    assert wire.parse_requests(wrap(base + huge)) is None
    # field 0 / huge field at the top level
    assert wire.parse_requests(b"\x00" + wrap(base)) is None
    assert wire.parse_requests(_varint((1 << 33) << 3 | 2) + _varint(0)) is None
    # protobuf agrees these are all malformed
    for data in (wrap(base + b"\x00"), wrap(base + huge)):
        with pytest.raises(Exception):
            pb.pb.GetRateLimitsReq.FromString(data)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_wire_type_mutation_fuzz(seed):
    """Differential fuzz with randomized wire types on every field: the
    columnar parser must agree with the protobuf object path whenever
    protobuf accepts the bytes, and must cleanly reject (None) or agree —
    never crash or mis-slice key bytes — when it does not."""
    rng = random.Random(seed)
    for _ in range(300):
        n_items = rng.randint(0, 4)
        body = b""
        expect_parseable = True
        for _i in range(n_items):
            inner = b""
            for _f in range(rng.randint(0, 8)):
                field = rng.randint(1, 12)
                wt = rng.choice([0, 0, 0, 2, 2, 1, 5, rng.choice([3, 4])])
                inner += _tag(field, wt)
                if wt == 0:
                    inner += _varint(rng.choice([0, 1, 7, 2**31, 2**63, 2**64 - 1]))
                elif wt == 1:
                    inner += rng.randbytes(8)
                elif wt == 5:
                    inner += rng.randbytes(4)
                elif wt == 2:
                    if field in (1, 2) and rng.random() < 0.7:
                        payload = bytes(
                            rng.choice(b"abcdefgh")
                            for _ in range(rng.randint(0, 6))
                        )
                    else:
                        payload = rng.randbytes(rng.randint(0, 12))
                    inner += _varint(len(payload)) + payload
                else:
                    expect_parseable = False  # group wire types: reject
            body += _tag(1, 2) + _varint(len(inner)) + inner
        try:
            msg = pb.pb.GetRateLimitsReq.FromString(body)
        except Exception:
            msg = None
        cols = wire.parse_requests(body)
        if cols is None:
            continue  # clean rejection -> object path handles it
        # key-buffer invariant must hold no matter what
        assert int(cols.key_offsets[-1]) <= len(cols.key_data)
        assert np.all(np.diff(cols.key_offsets) >= 0)
        if msg is None or not expect_parseable:
            continue
        assert cols.n == len(msg.requests)
        for i, req in enumerate(msg.requests):
            assert cols.key_string(i) == f"{req.name}_{req.unique_key}", (
                f"seed {seed} item {i}"
            )
            assert int(cols.hits[i]) == req.hits
            assert int(cols.limit[i]) == req.limit
            assert int(cols.duration[i]) == req.duration
            want_algo = req.algorithm & 0xFFFFFFFF
            if want_algo >= 1 << 31:
                want_algo -= 1 << 32
            assert int(cols.algo[i]) == want_algo
            assert int(cols.behavior[i]) == req.behavior
            assert int(cols.burst[i]) == req.burst


def test_mixed_ownership_split(loop_thread):
    """A V1 batch mixing locally-owned and peer-owned keys: local lanes
    decide columnar, the rest forward — responses splice in request
    order and counts match a fast-path-disabled cluster exactly."""
    import grpc as grpc_mod

    from gubernator_tpu.cluster import Cluster

    async def scenario():
        c = await Cluster.start(3, cache_size=4096)
        try:
            entry = c.daemons[0]
            # Build a batch with keys owned by ALL daemons. NOTE: fnv1
            # (like the reference's ring hash) has no avalanche on a
            # changing SUFFIX — sequential "mix0..mixN" keys land on one
            # ring arc — so vary the prefix to spread ownership.
            keys = [f"{i * 7919}mix" for i in range(30)]
            owners = {
                k: c.find_owning_daemon("mx", k).grpc_address for k in keys
            }
            assert len(set(owners.values())) >= 2
            msg = pb.pb.GetRateLimitsReq()
            for rep in range(3):  # duplicates exercise per-key sequencing
                for k in keys:
                    msg.requests.append(
                        pb.pb.RateLimitReq(
                            name="mx", unique_key=k, duration=600_000,
                            limit=100, hits=2,
                        )
                    )
            payload = msg.SerializeToString()
            async with grpc_mod.aio.insecure_channel(
                entry.grpc_address
            ) as ch:
                call = ch.unary_unary("/pb.gubernator.V1/GetRateLimits")
                raw = await call(payload)
            out = pb.pb.GetRateLimitsResp.FromString(raw)
            assert len(out.responses) == 90
            # Every key was hit 2x3 = 6 total, sequentially:
            # occurrences see remaining 98, 96, 94.
            for j, r in enumerate(out.responses):
                expect = 100 - 2 * (j // 30 + 1)
                assert r.remaining == expect, (j, r.remaining, expect)
            # And the fast path actually engaged for the local fraction.
            local_served = sum(
                d.svc.metrics.getratelimit_counter.labels("local").get()
                for d in c.daemons
            )
            assert local_served >= 90  # every item decided locally somewhere
        finally:
            await c.stop()

    loop_thread.run(scenario(), timeout=120)


def test_global_columnar_matches_object_path(loop_thread):
    """GLOBAL batches through the columnar fast edge must behave exactly
    like a fast-path-disabled cluster: same responses (owner metadata on
    non-owner answers included), same replica-local counting, and the
    same replication legs — hits reach the owner and the broadcast
    converges every replica."""
    import asyncio
    import time as _time

    import grpc as grpc_mod

    from gubernator_tpu.cluster import Cluster

    async def drive(fast: bool):
        c = await Cluster.start(3, cache_size=4096)
        try:
            if not fast:
                for d in c.daemons:
                    d.svc.fast_edge = False
            entry = c.daemons[0]
            keys = [f"{i * 7919}glb" for i in range(12)]
            owners = {
                k: c.find_owning_daemon("gl", k).grpc_address for k in keys
            }
            assert len(set(owners.values())) >= 2
            msg = pb.pb.GetRateLimitsReq()
            for rep in range(2):
                for j, k in enumerate(keys):
                    msg.requests.append(
                        pb.pb.RateLimitReq(
                            name="gl", unique_key=k, duration=600_000,
                            limit=100, hits=j % 3,  # incl. zero-hit reads
                            behavior=int(Behavior.GLOBAL),
                        )
                    )
            payload = msg.SerializeToString()
            async with grpc_mod.aio.insecure_channel(
                entry.grpc_address
            ) as ch:
                call = ch.unary_unary("/pb.gubernator.V1/GetRateLimits")
                raw = await call(payload)
            out = pb.pb.GetRateLimitsResp.FromString(raw)
            # cross-run comparable fields only: owner ADDRESSES (and which
            # keys are entry-local) differ between fresh clusters; the
            # metadata contract is asserted against THIS run's owners map
            # below.
            records = [
                (r.status, r.limit, r.remaining) for r in out.responses
            ]
            # metadata owner appears exactly on non-owner answers
            for j, r in enumerate(out.responses):
                k = keys[j % len(keys)]
                want = owners[k]
                got = dict(r.metadata).get("owner", "")
                if want == entry.grpc_address:
                    assert got == "", (j, got)
                else:
                    assert got == want, (j, got, want)
            if fast:
                # label parity: only NON-owner GLOBAL answers count as
                # "global" (owned GLOBAL items are "local", like the
                # object path's is_owner-first routing)
                want_glob = 2 * sum(
                    1 for k in keys if owners[k] != entry.grpc_address
                )
                glob_served = entry.svc.metrics.getratelimit_counter.labels(
                    "global"
                ).get()
                assert glob_served >= want_glob > 0, (glob_served, want_glob)
            # replication legs: every replica converges on the owner's
            # authoritative remaining (total hits per key = 2*(j%3))
            deadline = _time.monotonic() + 10
            want_rem = {
                k: 100 - 2 * (j % 3) for j, k in enumerate(keys)
            }
            while _time.monotonic() < deadline:
                probe = pb.pb.GetRateLimitsReq()
                for k in keys:
                    probe.requests.append(
                        pb.pb.RateLimitReq(
                            name="gl", unique_key=k, duration=600_000,
                            limit=100, hits=0,
                            behavior=int(Behavior.GLOBAL),
                        )
                    )
                async with grpc_mod.aio.insecure_channel(
                    c.daemons[2].grpc_address
                ) as ch:
                    call = ch.unary_unary("/pb.gubernator.V1/GetRateLimits")
                    praw = await call(probe.SerializeToString())
                pr = pb.pb.GetRateLimitsResp.FromString(praw)
                got_rem = {
                    k: r.remaining for k, r in zip(keys, pr.responses)
                }
                if got_rem == want_rem:
                    break
                await asyncio.sleep(0.1)
            assert got_rem == want_rem, (fast, got_rem, want_rem)
            return records
        finally:
            await c.stop()

    async def scenario():
        fast_records = await drive(True)
        slow_records = await drive(False)
        assert fast_records == slow_records

    loop_thread.run(scenario(), timeout=120)


@pytest.mark.parametrize("seed", [31])
def test_columns_adversarial_domain(seed):
    """In-domain adversarial values (limits near MAX_COUNT, huge hits,
    big time jumps): columnar and object paths must stay identical."""
    from gubernator_tpu.models.bucket import MAX_COUNT

    rng = random.Random(seed)
    clock = {"now": NOW}
    eng_a = mk_engine(clock)
    eng_b = mk_engine(clock)
    keys = [f"adv{i}" for i in range(6)]
    try:
        for step in range(60):
            if rng.random() < 0.25:
                clock["now"] += rng.choice([3, 900, 70_000, 10_000_000])
            batch = []
            for _ in range(rng.randint(1, 24)):
                b = 0
                if rng.random() < 0.12:
                    b |= Behavior.RESET_REMAINING
                if rng.random() < 0.12:
                    b |= Behavior.DRAIN_OVER_LIMIT
                batch.append(
                    RateLimitReq(
                        name="xf", unique_key=rng.choice(keys),
                        algorithm=rng.choice(
                            [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                        ),
                        behavior=b,
                        duration=rng.choice([50, 60_000, 3_600_000]),
                        limit=rng.choice([1, 7, MAX_COUNT, MAX_COUNT - 1]),
                        hits=rng.choice([-5, 0, 1, 120, 1 << 20]),
                        burst=rng.choice([0, 11, MAX_COUNT]),
                    )
                )
            cols = wire.parse_requests(to_proto_bytes(batch))
            got = eng_a.check_columns(cols, now=clock["now"])
            assert got is not None
            status, limit, remaining, reset_time = got
            want = eng_b.check_batch([dataclasses.replace(r) for r in batch])
            for i, w in enumerate(want):
                assert (
                    int(status[i]), int(limit[i]), int(remaining[i]),
                    int(reset_time[i]),
                ) == (int(w.status), w.limit, w.remaining, w.reset_time), (
                    f"seed {seed} step {step} item {i}: {batch[i]}"
                )
    finally:
        eng_a.close()
        eng_b.close()


def _mk_fast_svc(engine):
    """Minimal V1Service stand-in for fastpath.try_serve (standalone
    daemon: no picker/managers — owner of everything)."""
    from types import SimpleNamespace

    return SimpleNamespace(
        engine=engine, picker=None, region_mgr=None, global_mgr=None,
        fast_edge=True,
    )


def test_gregorian_lane_split_mixed_batch():
    """DURATION_IS_GREGORIAN items no longer demote the whole batch:
    plain lanes decide columnar and the Gregorian items come back as
    object-path requests through the mixed return, splicing in request
    order (the round-5 GLOBAL lane-split pattern)."""
    from gubernator_tpu.service import fastpath
    from gubernator_tpu.utils import gregorian as g

    clock = {"now": NOW}
    eng_a = mk_engine(clock)
    eng_b = mk_engine(clock)
    svc = _mk_fast_svc(eng_a)
    GREG = int(Behavior.DURATION_IS_GREGORIAN)
    try:
        batch = []
        for i in range(14):
            if i % 3 == 1:
                batch.append(
                    RateLimitReq(
                        name="greg", unique_key=f"g{i}", behavior=GREG,
                        duration=g.GREGORIAN_HOURS, limit=50, hits=2,
                    )
                )
            else:
                batch.append(
                    RateLimitReq(
                        name="fp", unique_key=f"k{i % 4}",
                        duration=60_000, limit=50, hits=1,
                    )
                )
        res = fastpath.try_serve(svc, to_proto_bytes(batch), False)
        assert isinstance(res, tuple) and res[0] == "mixed"
        _tag, n, local_pos, local_out, nl_reqs, md = res
        greg_pos = [i for i, r in enumerate(batch) if r.behavior & GREG]
        assert sorted(set(range(n)) - set(int(i) for i in local_pos)) == greg_pos
        # Object-path requests keep their behavior bits intact.
        assert all(r.behavior & GREG for r in nl_reqs)
        nl_resps = eng_a.check_batch(nl_reqs)  # the async caller's leg
        raw = fastpath.merge_mixed(n, local_pos, local_out, nl_resps, md)
        out = pb.pb.GetRateLimitsResp.FromString(raw)
        assert len(out.responses) == n
        want = eng_b.check_batch([dataclasses.replace(r) for r in batch])
        for i, (got, w) in enumerate(zip(out.responses, want)):
            assert (got.status, got.limit, got.remaining, got.reset_time) == (
                int(w.status), w.limit, w.remaining, w.reset_time,
            ), (i, batch[i])
    finally:
        eng_a.close()
        eng_b.close()


def test_gregorian_only_and_peer_batches_fall_back():
    """All-Gregorian batches have no columnar work; peer calls cannot
    return 'mixed' — both must take the whole-batch object path."""
    from gubernator_tpu.service import fastpath
    from gubernator_tpu.utils import gregorian as g

    clock = {"now": NOW}
    eng = mk_engine(clock)
    svc = _mk_fast_svc(eng)
    GREG = int(Behavior.DURATION_IS_GREGORIAN)
    try:
        greg = [
            RateLimitReq(
                name="greg", unique_key=f"g{i}", behavior=GREG,
                duration=g.GREGORIAN_DAYS, limit=5, hits=1,
            )
            for i in range(4)
        ]
        assert fastpath.try_serve(svc, to_proto_bytes(greg), False) is None
        mixed = greg + [
            RateLimitReq(name="fp", unique_key="p", duration=60_000, limit=5)
        ]
        assert fastpath.try_serve(svc, to_proto_bytes(mixed), True) is None
    finally:
        eng.close()


@pytest.mark.parametrize("seed", [31, 32])
def test_mixed_gregorian_fuzz(seed):
    """Fuzz the Gregorian lane split: random batches mixing plain and
    Gregorian items (distinct key spaces per lane, like real traffic)
    must decide identically to a pure object-path engine after the
    mixed-return splice."""
    from gubernator_tpu.service import fastpath
    from gubernator_tpu.utils import gregorian as g

    rng = random.Random(seed)
    clock = {"now": NOW}
    eng_a = mk_engine(clock)
    eng_b = mk_engine(clock)
    svc = _mk_fast_svc(eng_a)
    GREG = int(Behavior.DURATION_IS_GREGORIAN)
    try:
        for step in range(25):
            if rng.random() < 0.2:
                clock["now"] += rng.choice([5, 700, 70_000])
            batch = []
            for _ in range(rng.randint(2, 24)):
                if rng.random() < 0.3:
                    batch.append(
                        RateLimitReq(
                            name="greg", unique_key=f"g{rng.randint(0, 5)}",
                            behavior=GREG,
                            duration=rng.choice(
                                [g.GREGORIAN_MINUTES, g.GREGORIAN_HOURS,
                                 g.GREGORIAN_DAYS]
                            ),
                            limit=rng.choice([3, 10, 50]),
                            hits=rng.choice([0, 1, 2]),
                        )
                    )
                else:
                    batch.append(
                        RateLimitReq(
                            name="fp", unique_key=f"k{rng.randint(0, 7)}",
                            algorithm=rng.choice(
                                [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                            ),
                            duration=rng.choice([100, 60_000]),
                            limit=rng.choice([3, 10, 50]),
                            hits=rng.choice([0, 1, 2, 5]),
                        )
                    )
            res = fastpath.try_serve(svc, to_proto_bytes(batch), False)
            want = eng_b.check_batch([dataclasses.replace(r) for r in batch])
            if res is None:
                # all-Gregorian batch: the daemon's object path serves it
                assert all(r.behavior & GREG for r in batch)
                got = eng_a.check_batch([dataclasses.replace(r) for r in batch])
                rows = [
                    (int(r.status), r.limit, r.remaining, r.reset_time)
                    for r in got
                ]
            else:
                if isinstance(res, bytes):
                    assert not any(r.behavior & GREG for r in batch)
                    raw = res
                else:
                    _tag, n, local_pos, local_out, nl_reqs, md = res
                    nl_resps = eng_a.check_batch(nl_reqs)
                    raw = fastpath.merge_mixed(
                        n, local_pos, local_out, nl_resps, md
                    )
                out = pb.pb.GetRateLimitsResp.FromString(raw)
                rows = [
                    (r.status, r.limit, r.remaining, r.reset_time)
                    for r in out.responses
                ]
            for i, w in enumerate(want):
                assert rows[i] == (
                    int(w.status), w.limit, w.remaining, w.reset_time,
                ), (f"seed {seed} step {step} item {i}: {batch[i]}")
    finally:
        eng_a.close()
        eng_b.close()


@pytest.mark.parametrize("seed", [44, 45])
def test_thousand_item_zipf_calls_are_served_columnar(seed):
    """The fuzz at the API's cap: calls of 1,000 items drawn Zipf(0.99)
    from 4,000 keys hold their hottest key some 80 times, more than
    max_waves (32). Through fastpath.try_serve each is served columnar
    (until ISSUE 44: refused, reason `waves`) and its bytes equal the
    object path's answer serialized, over three calls in which the hot
    keys pass their limits."""
    from gubernator_tpu.service import fastpath
    from gubernator_tpu.utils import tracing

    rng = np.random.default_rng(seed)
    w = np.arange(1, 4_001, dtype=np.float64) ** -0.99
    cdf = np.cumsum(w) / w.sum()
    clock = {"now": NOW}
    mk = lambda: DeviceEngine(  # noqa: E731
        EngineConfig(num_groups=1 << 12, batch_size=1024, batch_wait_s=0.001),
        now_fn=lambda: clock["now"],
    )
    eng_a, eng_b = mk(), mk()
    svc = _mk_fast_svc(eng_a)
    try:
        for step in range(3):
            clock["now"] += 10
            ranks = np.minimum(np.searchsorted(cdf, rng.random(1000)), 3_999)
            batch = [
                RateLimitReq(
                    name="fp", unique_key=f"z{k}", duration=60_000,
                    limit=100, hits=int(h),
                    algorithm=(Algorithm.LEAKY_BUCKET if k % 2
                               else Algorithm.TOKEN_BUCKET),
                )
                for k, h in zip(ranks.tolist(), rng.integers(0, 3, 1000))
            ]
            assert max(np.bincount(ranks)) > eng_a.cfg.max_waves
            call = tracing.CallRecord({})
            raw = fastpath.try_serve(svc, to_proto_bytes(batch), False, call)
            assert (call.path, call.reason) == ("columnar", "")
            want = pb.pb.GetRateLimitsResp()
            for r in eng_b.check_batch(
                [dataclasses.replace(r) for r in batch]
            ):
                want.responses.append(pb.resp_to_pb(r))
            assert raw == want.SerializeToString(), f"seed {seed} step {step}"
            assert eng_a.metrics.recorder.last()["waves"] > eng_a.cfg.max_waves
        assert any(r.status for r in want.responses)  # some OVER_LIMIT
    finally:
        eng_a.close()
        eng_b.close()
