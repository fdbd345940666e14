"""Crash-tolerant ownership (parallel/standby.py, docs/robustness.md
"Standby replication & crash recovery"): wire codec + version skew,
receiver shadow semantics, promotion/echo idempotence, drain retire,
fault-injected repair, and the GUBER_STANDBY=0 bit-exact pin."""

import asyncio
import threading
import time
from types import SimpleNamespace

import grpc
import pytest

from gubernator_tpu.api.types import Algorithm
from gubernator_tpu.cluster import Cluster
from gubernator_tpu.parallel.standby import AE_REGIONS, ReplicationManager
from gubernator_tpu.service import pb
from gubernator_tpu.service.config import BehaviorConfig
from gubernator_tpu.store.store import ItemSnapshot
from gubernator_tpu.utils import faults

NAME = "standby_t"
LIMIT = 1_000_000
MINUTE = 60_000


def snap(key, stamp=1000, remaining=50, **kw):
    return ItemSnapshot(
        key=key, algorithm=int(Algorithm.TOKEN_BUCKET), limit=100,
        duration=600_000, remaining=remaining, stamp=stamp,
        expire_at=stamp + 600_000, **kw,
    )


# ---------------------------------------------------------------------------
# wire codec: v=2 envelope, malformed payloads, version fallthrough


def test_standby_wire_roundtrip():
    items = [snap("a_k1", stamp=123, remaining=7, burst=3),
             snap("b_k2", stamp=456, remaining=0, status=1)]
    digests = {0: (2, 12345), 63: (1, 999)}
    raw = pb.standby_to_bytes(
        "delta", "10.0.0.1:81", seq=7, snaps=items, digests=digests
    )
    out = pb.standby_from_bytes(raw)
    assert out["mode"] == "delta"
    assert out["owner"] == "10.0.0.1:81"
    assert out["seq"] == 7
    assert out["items"] == items
    assert out["digests"] == digests


def test_maybe_standby_falls_through_on_v1_payload():
    # A plain v=1 snapshot transfer is NOT a standby envelope: the
    # TransferSnapshots servicer must fall through to the v1 decoder.
    assert pb.maybe_standby_from_bytes(pb.snapshots_to_bytes([snap("a")])) is None
    # Garbage that isn't JSON belongs to the v1 decoder's typed error.
    assert pb.maybe_standby_from_bytes(b"not json") is None
    assert pb.maybe_standby_from_bytes(b"\xff\xfe\x00") is None


def test_standby_wire_rejects_malformed():
    good = pb.standby_to_bytes("delta", "o", seq=1, snaps=[snap("a")])
    # Truncation makes it non-JSON: falls to the v1 decoder (None), and
    # the strict decoder raises a typed error — never a hang or a crash.
    assert pb.maybe_standby_from_bytes(good[:-4]) is None
    with pytest.raises(ValueError):
        pb.standby_from_bytes(good[:-4])
    # Standby-shaped but wrong version / bad mode / mangled rows are a
    # typed ValueError from BOTH decoders.
    for raw in (
        b'{"kind": "standby", "v": 999, "mode": "delta", "owner": "o"}',
        b'{"kind": "standby", "v": 2, "mode": "bogus", "owner": "o"}',
        b'{"kind": "standby", "v": 2, "mode": "delta"}',
        b'{"kind": "standby", "v": 2, "mode": "delta", "owner": "o", "items": [["k", 1]]}',
        b'{"kind": "standby", "v": 2, "mode": "digest", "owner": "o", "digests": {"x": [1]}}',
    ):
        with pytest.raises(ValueError):
            pb.maybe_standby_from_bytes(raw)
        with pytest.raises(ValueError):
            pb.standby_from_bytes(raw)


# ---------------------------------------------------------------------------
# receiver shadow semantics (no cluster: fake svc/mesh)


class _FakeMetric:
    def __init__(self):
        self.value = 0.0

    def set(self, v):
        self.value = v

    def inc(self, n=1):
        self.value += n

    def labels(self, *a):
        return self


def _manager(**behavior_kw):
    b = BehaviorConfig(**behavior_kw)
    metrics = SimpleNamespace(
        standby_loss_bound_hits=_FakeMetric(),
        standby_shadow_keys=_FakeMetric(),
        standby_keys_shipped=_FakeMetric(),
        standby_ship_errors=_FakeMetric(),
        standby_promotions=_FakeMetric(),
        standby_promoted_keys=_FakeMetric(),
        standby_anti_entropy_repairs=_FakeMetric(),
        consistency_divergence=_FakeMetric(),
    )
    svc = SimpleNamespace(metrics=metrics, engine=None)
    import zlib

    mesh = SimpleNamespace(hash_fn=lambda k: zlib.crc32(k.encode()))
    return ReplicationManager(svc, b, local_addr="local:1", mesh=mesh)


def test_receive_delta_applies_lww():
    rm = _manager()
    a, s1, _ = rm.receive(pb.standby_from_bytes(
        pb.standby_to_bytes("delta", "o:1", seq=1,
                            snaps=[snap("k", stamp=100, remaining=80)])))
    assert (a, s1) == (1, 0)
    # Older stamp: stale. Equal stamp, MORE remaining (less consumed):
    # stale — the more-consumed copy carries the true count.
    for s in (snap("k", stamp=50, remaining=10),
              snap("k", stamp=100, remaining=90)):
        a, st, _ = rm.receive(pb.standby_from_bytes(
            pb.standby_to_bytes("delta", "o:1", seq=2, snaps=[s])))
        assert (a, st) == (0, 1)
    # Equal stamp, less remaining (more consumed): wins.
    a, st, _ = rm.receive(pb.standby_from_bytes(
        pb.standby_to_bytes("delta", "o:1", seq=3,
                            snaps=[snap("k", stamp=100, remaining=70)])))
    assert (a, st) == (1, 0)
    with rm._shadow_lock:
        assert rm._shadow["o:1"].rows["k"].remaining == 70


def test_receive_full_replaces_and_region_purge():
    rm = _manager()
    rm.receive(pb.standby_from_bytes(pb.standby_to_bytes(
        "delta", "o:1", seq=1, snaps=[snap("gone"), snap("kept")])))
    # Plain full image: wholesale replace.
    rm.receive(pb.standby_from_bytes(pb.standby_to_bytes(
        "full", "o:1", seq=2, snaps=[snap("fresh")])))
    with rm._shadow_lock:
        assert set(rm._shadow["o:1"].rows) == {"fresh"}
    # Region-scoped replace (anti-entropy repair): only rows in the
    # digest-keyed regions are purged before the insert.
    region = rm._region("fresh")
    other = next(
        f"o{i}" for i in range(10_000) if rm._region(f"o{i}") != region
    )
    rm.receive(pb.standby_from_bytes(pb.standby_to_bytes(
        "delta", "o:1", seq=3, snaps=[snap(other)])))
    rm.receive(pb.standby_from_bytes(pb.standby_to_bytes(
        "full", "o:1", seq=4, snaps=[], digests={region: (0, 0)})))
    with rm._shadow_lock:
        assert set(rm._shadow["o:1"].rows) == {other}


def test_receive_digest_reports_mismatched_regions():
    rm = _manager()
    rows = [snap(f"k{i}", stamp=100 + i) for i in range(8)]
    rm.receive(pb.standby_from_bytes(pb.standby_to_bytes(
        "full", "o:1", seq=1, snaps=rows)))
    # Matching digests: no mismatch.
    d = rm._compute_digests(rows)
    _, _, extra = rm.receive(pb.standby_from_bytes(pb.standby_to_bytes(
        "digest", "o:1", seq=2, digests=d)))
    assert extra["standby"]["mismatch"] == []
    # Drop one shadow row: exactly its region mismatches (both ways —
    # also regions the owner has that the shadow lacks entirely).
    victim = rows[3]
    with rm._shadow_lock:
        del rm._shadow["o:1"].rows[victim.key]
    _, _, extra = rm.receive(pb.standby_from_bytes(pb.standby_to_bytes(
        "digest", "o:1", seq=3, digests=d)))
    assert extra["standby"]["mismatch"] == [rm._region(victim.key)]
    assert all(0 <= r < AE_REGIONS for r in extra["standby"]["mismatch"])


def test_receive_retire_drops_shadow_and_cap_counts_drops():
    rm = _manager(standby_max_keys=2)
    rm.receive(pb.standby_from_bytes(pb.standby_to_bytes(
        "delta", "o:1", seq=1,
        snaps=[snap("a"), snap("b"), snap("c")])))
    with rm._shadow_lock:
        ent = rm._shadow["o:1"]
    assert len(ent.rows) == 2 and ent.dropped == 1
    # Updates to EXISTING keys still apply at the cap.
    a, st, _ = rm.receive(pb.standby_from_bytes(pb.standby_to_bytes(
        "delta", "o:1", seq=2, snaps=[snap("a", stamp=2000)])))
    assert (a, st) == (1, 0)
    _, _, extra = rm.receive(pb.standby_from_bytes(pb.standby_to_bytes(
        "retire", "o:1", seq=3)))
    assert extra["standby"]["retired"] == 2
    with rm._shadow_lock:
        assert "o:1" not in rm._shadow


def test_ring_change_shadow_probe_holds_lock():
    """Regression: on_ring_change probed `addr in self._shadow` without
    the shadow lock while executor-thread receive() mutates it. The
    race sanitizer (on suite-wide, tests/conftest.py) records any
    unlocked probe — this test fails pre-fix via the explicit assert
    below AND the autouse graph check."""
    from gubernator_tpu.utils import raceguard

    rm = _manager()
    rm.receive(pb.standby_from_bytes(pb.standby_to_bytes(
        "delta", "o:1", seq=1, snaps=[snap("a")])))
    rm.on_ring_change({"o:1", "o:2"}, set())
    # a departed source with a live shadow is queued; one without isn't
    assert rm._promote_queue == {"o:1"}
    assert raceguard.DEFAULT_GRAPH.report() == []


def test_scan_promotions_shadow_scan_holds_lock():
    """Same regression for _scan_promotions' membership probe and keys
    iteration (both read _shadow from the loop thread while executor
    receives land)."""
    from gubernator_tpu.utils import raceguard

    rm = _manager()
    rm.receive(pb.standby_from_bytes(pb.standby_to_bytes(
        "delta", "o:1", seq=1, snaps=[snap("a")])))
    rm.mesh._all = {
        "o:1": SimpleNamespace(
            breaker=SimpleNamespace(state_name="closed")
        )
    }
    asyncio.run(rm._scan_promotions())
    assert raceguard.DEFAULT_GRAPH.report() == []


@pytest.mark.chaos
def test_loss_bound_scrape_survives_ledger_resize():
    """loss_bound_hits() is scraped off the loop thread while the ship
    loop mutates the ledger. The audit verdict: the old values() sum
    was GIL-atomic in CPython (one C-level call), so this pins the
    contract rather than a reproducible pre-fix crash — the dict() copy
    keeps the read one atomic snapshot even on runtimes where C loops
    can interleave (free-threaded builds)."""
    rm = _manager()
    errors = []

    def scraper():
        try:
            for _ in range(2000):
                rm.loss_bound_hits()
        except RuntimeError as e:  # pragma: no cover - pre-fix only
            errors.append(e)

    t = threading.Thread(target=scraper)
    t.start()
    # Play the ship loop: grow then clear so the dict RESIZES (resize
    # mid-iteration is what raises on the pre-fix read).
    i = 0
    while t.is_alive():
        for j in range(64):
            rm._pending_hits[f"k{i}:{j}"] = 1
        rm._pending_hits.clear()
        i += 1
    t.join(timeout=10)
    assert not errors, errors


# ---------------------------------------------------------------------------
# env knobs


def test_envconfig_standby_knobs(monkeypatch):
    from gubernator_tpu.service.envconfig import setup_daemon_config

    monkeypatch.setenv("GUBER_STANDBY", "1")
    monkeypatch.setenv("GUBER_STANDBY_INTERVAL", "250ms")
    monkeypatch.setenv("GUBER_STANDBY_FACTOR", "2")
    monkeypatch.setenv("GUBER_STANDBY_PROMOTE_AFTER", "1500ms")
    monkeypatch.setenv("GUBER_STANDBY_ANTI_ENTROPY_INTERVAL", "5s")
    monkeypatch.setenv("GUBER_STANDBY_MAX_KEYS", "777")
    b = setup_daemon_config().behaviors
    assert b.standby is True
    assert b.standby_interval_s == pytest.approx(0.25)
    assert b.standby_factor == 2
    assert b.standby_promote_after_s == pytest.approx(1.5)
    assert b.standby_anti_entropy_interval_s == pytest.approx(5.0)
    assert b.standby_max_keys == 777

    monkeypatch.setenv("GUBER_STANDBY_FACTOR", "0")
    with pytest.raises(ValueError, match="GUBER_STANDBY_FACTOR"):
        setup_daemon_config()
    monkeypatch.setenv("GUBER_STANDBY_FACTOR", "1")
    monkeypatch.setenv("GUBER_STANDBY_PROMOTE_AFTER", "0")
    with pytest.raises(ValueError, match="GUBER_STANDBY_PROMOTE_AFTER"):
        setup_daemon_config()
    # With standby OFF the sub-knobs are unvalidated inert state.
    monkeypatch.setenv("GUBER_STANDBY", "0")
    assert setup_daemon_config().behaviors.standby is False


# ---------------------------------------------------------------------------
# cluster-level (chaos marker: deterministic fault-injection subset)

FAST = dict(
    standby_interval_s=0.1,
    standby_promote_after_s=0.5,
    standby_anti_entropy_interval_s=0.0,  # driven manually
    circuit_failure_threshold=2,
    circuit_open_base_s=0.2,
    circuit_open_max_s=0.5,
)


def _hit(loop_thread, daemon, key, hits, name=NAME):
    async def call():
        msg = pb.pb.GetRateLimitsReq()
        msg.requests.append(
            pb.pb.RateLimitReq(
                name=name, unique_key=key, duration=10 * MINUTE,
                limit=LIMIT, hits=hits,
            )
        )
        return (await daemon.client().get_rate_limits(msg, timeout=10)).responses[0]

    return loop_thread.run(call())


def _victim_keys(c, n=24):
    victim = c.find_owning_daemon(NAME, "vk")
    keys = []
    for i in range(100_000):
        k = f"sk{i}"
        if c.find_owning_daemon(NAME, k) is victim:
            keys.append(k)
            if len(keys) >= n:
                break
    return victim, keys


@pytest.mark.chaos
def test_hard_kill_promotion_no_double_count(loop_thread):
    c = loop_thread.run(
        Cluster.start(3, behaviors=BehaviorConfig(**FAST)), timeout=120
    )
    try:
        victim, keys = _victim_keys(c)
        survivors = [d for d in c.daemons if d is not victim]
        driver = survivors[0]
        sent = {}
        for i, k in enumerate(keys):
            resp = _hit(loop_thread, driver, k, 3 + (i % 4))
            assert not resp.error
            sent[k] = 3 + (i % 4)
        # Quiesce: everything ships and acks, the bound drains to 0.
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if victim.svc.standby.loss_bound_hits() == 0:
                break
            time.sleep(0.05)
        assert victim.svc.standby.loss_bound_hits() == 0
        # Hard kill: freeze replication, partition, drop from the ring.
        sb = victim._standby
        loop_thread.run(_cancel_tasks(sb))
        faults.INJECTOR.partition(victim.grpc_address)
        victim_addr = victim.grpc_address
        c.daemons.remove(victim)
        c.rewire()
        deadline = time.monotonic() + 8
        while time.monotonic() < deadline:
            if all(
                victim_addr not in d.svc.standby.summary()["shadows"]
                for d in survivors
            ):
                break
            time.sleep(0.05)
        # Zero loss (quiesced before the kill) AND no double count: the
        # promoted state answers with EXACTLY the consumed hits — not
        # fewer (lost) and not more (replayed twice). A second promotion
        # or a handover echo merging again would show up here.
        for k, n in sent.items():
            resp = _hit(loop_thread, driver, k, 0)
            assert not resp.error
            assert LIMIT - resp.remaining == n, k
        assert sum(
            d.svc.standby.summary()["promotions"] for d in survivors
        ) >= 1
        loop_thread.run(victim.close())
    finally:
        faults.INJECTOR.clear()
        loop_thread.run(c.stop())


async def _cancel_tasks(sb):
    for t in (sb._ship_task, sb._ae_task):
        if t is not None:
            t.cancel()
    sb._ship_task = sb._ae_task = None


@pytest.mark.chaos
def test_graceful_drain_retires_shadow(loop_thread):
    c = loop_thread.run(
        Cluster.start(3, behaviors=BehaviorConfig(**FAST)), timeout=120
    )
    try:
        victim, keys = _victim_keys(c, n=8)
        survivors = [d for d in c.daemons if d is not victim]
        driver = survivors[0]
        for k in keys:
            assert not _hit(loop_thread, driver, k, 5).error
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if any(
                victim.grpc_address in d.svc.standby.summary()["shadows"]
                for d in survivors
            ):
                break
            time.sleep(0.05)
        # Graceful exit: decommission (ring change ships state via
        # handover) then close — the standby retires its shadows first,
        # so the drained state can never be replayed by a promotion.
        victim_addr = victim.grpc_address
        c.daemons.remove(victim)
        c.rewire()
        loop_thread.run(victim.close(), timeout=60)
        for d in survivors:
            assert victim_addr not in d.svc.standby.summary()["shadows"]
        # State handed over exactly once.
        for k in keys:
            resp = _hit(loop_thread, driver, k, 0)
            assert not resp.error
            assert LIMIT - resp.remaining == 5, k
        assert sum(
            d.svc.standby.summary()["promotions"] for d in survivors
        ) == 0
    finally:
        loop_thread.run(c.stop())


@pytest.mark.chaos
def test_standby_fault_drops_repaired_by_anti_entropy(loop_thread):
    c = loop_thread.run(
        Cluster.start(2, behaviors=BehaviorConfig(**FAST)), timeout=120
    )
    try:
        a, b = c.daemons
        a_keys = [
            k for k in (f"ae{i}" for i in range(4000))
            if c.find_owning_daemon(NAME, k) is a
        ][:16]
        # Drop the standby leg entirely while the first rows ship: the
        # faults.OP_PEER_STANDBY hook makes replication chaos-testable
        # without touching serving traffic.
        faults.INJECTOR.add_rule(faults.FaultRule(
            target=b.grpc_address, op=faults.OP_PEER_STANDBY,
            error_rate=1.0, max_injections=3,
        ))
        for k in a_keys:
            assert not _hit(loop_thread, a, k, 7).error
        # Ships retry (failed keys stay pending), so the shadow heals
        # once the fault budget is exhausted.
        deadline = time.monotonic() + 8
        while time.monotonic() < deadline:
            if a.svc.standby.loss_bound_hits() == 0:
                break
            time.sleep(0.05)
        assert a.svc.standby.loss_bound_hits() == 0
        faults.INJECTOR.clear()
        # Corrupt the shadow (simulated standby restart): anti-entropy
        # must find and repair it, then report clean.
        with b.svc.standby._shadow_lock:
            shadow = b.svc.standby._shadow[a.grpc_address]
            lost = list(shadow.rows)[:4]
            for k in lost:
                del shadow.rows[k]
        r1 = loop_thread.run(a.svc.standby.anti_entropy_once(), timeout=30)
        assert r1["mismatched_regions"] > 0
        r2 = loop_thread.run(a.svc.standby.anti_entropy_once(), timeout=30)
        assert r2["mismatched_regions"] == 0
        with b.svc.standby._shadow_lock:
            for k in lost:
                assert k in b.svc.standby._shadow[a.grpc_address].rows
    finally:
        faults.INJECTOR.clear()
        loop_thread.run(c.stop())


@pytest.mark.chaos
def test_standby_off_is_bit_exact(loop_thread):
    c = loop_thread.run(
        Cluster.start(
            2, behaviors=BehaviorConfig(standby=False, **{
                k: v for k, v in FAST.items() if not k.startswith("standby")
            })
        ),
        timeout=120,
    )
    try:
        a, b = c.daemons
        # No manager, no dirty tracking, no debug surface.
        for d in (a, b):
            assert d.svc.standby is None
            with d.engine._dirty_lock:
                assert d.engine._dirty is None
            assert d.svc.standby_debug_info() == {"enabled": False}
        assert not _hit(loop_thread, a, "off_k", 3).error
        # A v=2 envelope is rejected INVALID_ARGUMENT — the same class a
        # pre-standby build produces, so a skewed sender falls back to
        # v=1 (which still works: the LWW serving-table merge).
        peer = a.svc.picker._all[b.grpc_address]

        async def send_v2():
            await peer.standby_transfer(pb.standby_to_bytes(
                "delta", a.grpc_address, seq=1, snaps=[snap("x")]))

        with pytest.raises(grpc.aio.AioRpcError) as ei:
            loop_thread.run(send_v2())
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT

        async def send_v1():
            return await peer.standby_transfer(
                pb.snapshots_to_bytes([snap("legacy_k", stamp=int(
                    time.time() * 1000) + 60_000)]))

        resp = loop_thread.run(send_v1())
        assert resp["accepted"] == 1
    finally:
        loop_thread.run(c.stop())


@pytest.mark.chaos
def test_malformed_standby_payload_typed_error(loop_thread):
    c = loop_thread.run(
        Cluster.start(2, behaviors=BehaviorConfig(**FAST)), timeout=120
    )
    try:
        a, b = c.daemons
        peer = a.svc.picker._all[b.grpc_address]

        async def send(raw):
            await peer.standby_transfer(raw)

        # Standby-shaped but malformed / wrong version: typed
        # INVALID_ARGUMENT carrying the decode error, never a hang.
        for raw in (
            b'{"kind": "standby", "v": 999, "mode": "delta", "owner": "o"}',
            b'{"kind": "standby", "v": 2, "mode": "delta", "owner": "o", "items": [["k"]]}',
        ):
            with pytest.raises(grpc.aio.AioRpcError) as ei:
                loop_thread.run(send(raw), timeout=30)
            assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        # Plain garbage falls through to the v1 decoder's typed error.
        with pytest.raises(grpc.aio.AioRpcError) as ei:
            loop_thread.run(send(b"\x00\x01garbage"), timeout=30)
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    finally:
        loop_thread.run(c.stop())
