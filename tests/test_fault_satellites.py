"""Satellite coverage for the fault-domain PR: healthcheck probe
address resolution, edge-tier timeout observability, GLOBAL hit-update
drop accounting (no_peer) and requeue aging caps, and the /livez +
/readyz probe routes on a plain daemon."""

import asyncio
import struct

import pytest

from gubernator_tpu.api.types import Behavior, RateLimitReq
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.service.config import BehaviorConfig

pytestmark = pytest.mark.chaos


# ---- cmd/healthcheck address resolution ------------------------------------


def test_healthcheck_prefers_status_listener(monkeypatch):
    from gubernator_tpu.cmd import healthcheck

    monkeypatch.setenv("GUBER_HTTP_ADDRESS", "1.2.3.4:80")
    monkeypatch.setenv("GUBER_STATUS_HTTP_ADDRESS", "1.2.3.4:9090")
    assert healthcheck.default_url() == "http://1.2.3.4:9090/v1/HealthCheck"
    monkeypatch.delenv("GUBER_STATUS_HTTP_ADDRESS")
    monkeypatch.setenv("GUBER_STATUS_LISTEN_ADDRESS", "1.2.3.4:9191")
    assert healthcheck.default_url() == "http://1.2.3.4:9191/v1/HealthCheck"
    monkeypatch.delenv("GUBER_STATUS_LISTEN_ADDRESS")
    assert healthcheck.default_url() == "http://1.2.3.4:80/v1/HealthCheck"
    monkeypatch.delenv("GUBER_HTTP_ADDRESS")
    assert healthcheck.default_url() == "http://127.0.0.1:80/v1/HealthCheck"


def test_healthcheck_timeout_flag_applies(monkeypatch):
    from gubernator_tpu.cmd import healthcheck

    seen = {}

    def fake_urlopen(url, timeout=None):
        seen["timeout"] = timeout
        raise OSError("probe refused")

    monkeypatch.setattr(
        "gubernator_tpu.cmd.healthcheck.urllib.request.urlopen", fake_urlopen
    )
    rc = healthcheck.main(["--url", "http://x/v1/HealthCheck", "--timeout", "0.25"])
    assert rc == 1
    assert seen["timeout"] == 0.25


# ---- EdgeClient timeout: configured, counted -------------------------------


@pytest.mark.deadline(30)
def test_edge_client_timeout_sourced_and_counted():
    from gubernator_tpu.service.edge import (
        METHOD_HEALTH_CHECK,
        EdgeClient,
        EdgeError,
    )

    async def main():
        # A server that accepts frames and never answers: the stall case.
        # It closes its writer once the client goes away — on Python
        # 3.12 Server.wait_closed() waits for every connection.
        async def black_hole(reader, writer):
            try:
                while await reader.read(4096):
                    pass
            except ConnectionResetError:
                pass
            finally:
                writer.close()

        server = await asyncio.start_server(black_hole, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        metrics = Metrics()
        client = EdgeClient(
            f"127.0.0.1:{port}",
            connections=1,
            timeout_s=0.1,
            timeout_counter=metrics.edge_call_timeouts,
        )
        try:
            with pytest.raises(EdgeError) as ei:
                await client.call(METHOD_HEALTH_CHECK, b"")
            assert ei.value.code == "DEADLINE_EXCEEDED"
            assert metrics.edge_call_timeouts.labels().get() == 1
            # Explicit per-call timeout still overrides the default.
            with pytest.raises(EdgeError):
                await client.call(METHOD_HEALTH_CHECK, b"", timeout=0.05)
            assert metrics.edge_call_timeouts.labels().get() == 2
        finally:
            await client.close()
            server.close()
            await server.wait_closed()

    asyncio.run(main())


def test_edge_behavior_config_carries_timeout():
    assert BehaviorConfig().edge_timeout_s == 30.0
    assert BehaviorConfig(edge_timeout_s=1.5).edge_timeout_s == 1.5


# ---- GLOBAL hit-update drop accounting and requeue aging -------------------


class _FakePicker:
    def __init__(self, peer=None, raise_for=()):
        self.peer = peer
        self.raise_for = set(raise_for)

    def get(self, key):
        if self.peer is None or key in self.raise_for:
            raise RuntimeError("no owner in ring")
        return self.peer


class _FakePeer:
    def __init__(self, addr="10.0.0.1:81", fail=True):
        self.info = type("I", (), {"grpc_address": addr, "is_owner": False})()
        self.fail = fail
        self.calls = 0

    async def get_peer_rate_limits(self, reqs, timeout=None):
        self.calls += 1
        if self.fail:
            raise RuntimeError("owner dark")
        return []


class _FakeSvc:
    def __init__(self, picker):
        self.metrics = Metrics()
        self.picker = picker
        self.forwarder = None
        self.engine = None


def _req(key, hits=1):
    return RateLimitReq(
        name="gq", unique_key=key, hits=hits, limit=100, duration=60_000,
        behavior=int(Behavior.GLOBAL),
    )


def test_send_hits_counts_no_peer_drops():
    from gubernator_tpu.parallel.global_sync import GlobalManager

    async def main():
        svc = _FakeSvc(_FakePicker(peer=None))
        mgr = GlobalManager(svc, BehaviorConfig(global_sync_wait_s=60.0))
        try:
            await mgr._send_hits({"gq_a": _req("a", 3), "gq_b": _req("b", 2)})
            assert (
                svc.metrics.global_send_dropped.labels("no_peer").get() == 5
            ), "picker failures must count every dropped hit"
            assert mgr.hits == {}, "no_peer hits are unroutable: not requeued"
        finally:
            await mgr.close()

    asyncio.run(main())


def test_failed_flush_requeues_and_ages_out():
    from gubernator_tpu.parallel.global_sync import GlobalManager

    async def main():
        peer = _FakePeer(fail=True)
        svc = _FakeSvc(_FakePicker(peer=peer))
        mgr = GlobalManager(
            svc,
            BehaviorConfig(global_sync_wait_s=60.0, global_requeue_limit=2),
        )
        try:
            await mgr._send_hits({"gq_a": _req("a", 4)})
            # attempt 1 failed -> requeued with the hits intact
            assert mgr.hits["gq_a"].hits == 4
            assert svc.metrics.global_requeued_hits.labels().get() == 4
            # fresh traffic merges into the requeued entry
            mgr.queue_hit(_req("a", 1))
            assert mgr.hits["gq_a"].hits == 5

            take = dict(mgr.hits)
            mgr.hits.clear()
            await mgr._send_hits(take)  # attempt 2: still failing
            assert mgr.hits["gq_a"].hits == 5

            take = dict(mgr.hits)
            mgr.hits.clear()
            await mgr._send_hits(take)  # attempt 3 > limit: dropped
            assert "gq_a" not in mgr.hits
            assert (
                svc.metrics.global_send_dropped.labels("requeue_cap").get() == 5
            )

            # recovery path: a successful send clears the age so the key
            # starts fresh on its next failure
            peer.fail = False
            mgr.queue_hit(_req("a", 1))
            take = dict(mgr.hits)
            mgr.hits.clear()
            await mgr._send_hits(take)
            assert mgr._requeue_counts == {}
        finally:
            await mgr.close()

    asyncio.run(main())


def test_requeue_key_cap_bounds_memory():
    from gubernator_tpu.parallel.global_sync import GlobalManager

    async def main():
        peer = _FakePeer(fail=True)
        svc = _FakeSvc(_FakePicker(peer=peer))
        mgr = GlobalManager(
            svc,
            BehaviorConfig(
                global_sync_wait_s=60.0,
                global_requeue_limit=100,
                global_requeue_max_keys=3,
            ),
        )
        try:
            await mgr._send_hits({f"gq_k{i}": _req(f"k{i}") for i in range(5)})
            assert len(mgr.hits) == 3, "redelivery queue must stay bounded"
            assert (
                svc.metrics.global_send_dropped.labels("requeue_cap").get() == 2
            )
        finally:
            await mgr.close()

    asyncio.run(main())


def test_circuit_open_skip_does_not_age_keys():
    from gubernator_tpu.parallel.global_sync import GlobalManager
    from gubernator_tpu.utils.breaker import CircuitBreaker

    async def main():
        peer = _FakePeer(fail=True)
        # An open breaker on the peer: sends are skipped, not attempted.
        peer.breaker = CircuitBreaker(failure_threshold=1, open_base_s=60.0)
        peer.breaker.record_failure()
        svc = _FakeSvc(_FakePicker(peer=peer))
        mgr = GlobalManager(
            svc,
            BehaviorConfig(global_sync_wait_s=60.0, global_requeue_limit=1),
        )
        try:
            for _ in range(5):  # far past the aging limit
                take = dict(mgr.hits) or {"gq_a": _req("a", 2)}
                mgr.hits.clear()
                await mgr._send_hits(take)
            assert peer.calls == 0, "open circuit must skip the RPC"
            assert mgr.hits["gq_a"].hits == 2, (
                "circuit-open skips must not age hits out of the queue"
            )
        finally:
            await mgr.close()

    asyncio.run(main())


# ---- env knob parsing ------------------------------------------------------


def test_envconfig_fault_domain_knobs(monkeypatch):
    from gubernator_tpu.service.envconfig import setup_daemon_config

    monkeypatch.setenv("GUBER_FORWARD_DEADLINE", "750ms")
    monkeypatch.setenv("GUBER_CIRCUIT_FAILURE_THRESHOLD", "7")
    monkeypatch.setenv("GUBER_CIRCUIT_OPEN_BASE", "250ms")
    monkeypatch.setenv("GUBER_CIRCUIT_OPEN_MAX", "10s")
    monkeypatch.setenv("GUBER_CIRCUIT_HALF_OPEN_PROBES", "2")
    monkeypatch.setenv("GUBER_OWNER_UNREACHABLE", "local")
    monkeypatch.setenv("GUBER_GLOBAL_REQUEUE_LIMIT", "4")
    monkeypatch.setenv("GUBER_GLOBAL_REQUEUE_MAX_KEYS", "123")
    monkeypatch.setenv("GUBER_EDGE_TIMEOUT", "5s")
    b = setup_daemon_config().behaviors
    assert b.forward_deadline_s == pytest.approx(0.75)
    assert b.circuit_failure_threshold == 7
    assert b.circuit_open_base_s == pytest.approx(0.25)
    assert b.circuit_open_max_s == pytest.approx(10.0)
    assert b.circuit_half_open_probes == 2
    assert b.owner_unreachable == "local"
    assert b.global_requeue_limit == 4
    assert b.global_requeue_max_keys == 123
    assert b.edge_timeout_s == pytest.approx(5.0)

    monkeypatch.setenv("GUBER_OWNER_UNREACHABLE", "bogus")
    with pytest.raises(ValueError, match="GUBER_OWNER_UNREACHABLE"):
        setup_daemon_config()


# ---- /livez + /readyz on a meshless daemon ---------------------------------


def test_probe_routes_on_standalone_daemon(loop_thread):
    import requests

    from gubernator_tpu.service.config import DaemonConfig
    from gubernator_tpu.service.daemon import Daemon

    d = loop_thread.run(Daemon.spawn(DaemonConfig(cache_size=1024)), timeout=120)
    try:
        r = requests.get(f"http://{d.http_address}/livez", timeout=5)
        assert (r.status_code, r.text) == (200, "ok")
        r = requests.get(f"http://{d.http_address}/readyz", timeout=5)
        assert r.status_code == 200
        body = r.json()
        # A daemon whose mesh is only itself is trivially ready.
        assert body["status"] == "ready" and body["open_circuits"] == []
    finally:
        loop_thread.run(d.close())
