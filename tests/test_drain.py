"""Graceful drain shutdown (docs/robustness.md "Rolling restarts &
handover"): Engine.close() serves its queue before failing stragglers
with the typed retryable status; Daemon.close() drains in-flight RPCs
with zero failures; /readyz and cmd/healthcheck distinguish `draining`
from `unready`; the peer forward queue sheds instead of blocking."""

import asyncio

import pytest
import requests

from gubernator_tpu.api.types import (
    ERR_ENGINE_DRAINING,
    RateLimitReq,
    is_retryable_error,
)
from gubernator_tpu.cluster import Cluster
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig


def _req(i, hits=1):
    return RateLimitReq(
        name="drain", unique_key=f"k{i}", duration=600_000, limit=10_000,
        hits=hits,
    )


def test_engine_close_drains_queue():
    """Everything enqueued before close() is SERVED, not failed — the
    pump finishes its queue on shutdown (zero-loss drain)."""
    eng = DeviceEngine(EngineConfig(num_groups=256, batch_size=128))
    try:
        futs = [eng.check_async(_req(i)) for i in range(400)]
    finally:
        eng.close()
    for f in futs:
        resp = f.result(timeout=1)
        assert resp.error == "", resp
        assert resp.remaining == 9_999


def test_engine_close_syncs_inflight_tickets_zero_loss():
    """Dispatched-but-unsynced pipeline tickets are completed — not
    failed — on close(): the drain covers the in-flight ring, not just
    the intake queue (ISSUE 6: zero-loss elasticity must survive
    pipelining)."""
    import threading

    eng = DeviceEngine(
        EngineConfig(
            num_groups=256, batch_size=128, batch_wait_s=0.0005,
            pipeline_depth=4,
        )
    )
    gate = threading.Event()
    orig = eng._complete

    def gated(t):
        gate.wait(10)
        orig(t)

    eng._complete = gated
    try:
        futs = [eng.check_async(_req(i)) for i in range(200)]
        # Let the pump fill the in-flight ring, then release completion
        # shortly AFTER close() starts so the quiesce genuinely waits on
        # in-flight tickets.
        threading.Timer(0.3, gate.set).start()
    finally:
        eng.close()
    for f in futs:
        resp = f.result(timeout=1)
        assert resp.error == "", resp
        assert resp.remaining == 9_999


def test_engine_close_stragglers_get_typed_retryable_error():
    """Past the drain budget, stragglers fail with the typed retryable
    status (not the old bare \"engine shutdown\" string) so edges and
    clients can re-dispatch."""
    eng = DeviceEngine(
        EngineConfig(num_groups=256, batch_size=128, drain_timeout_s=0.0)
    )
    # Make the pump unable to place anything: every flush carries the
    # whole batch, so close() hits the (zero) drain budget with work
    # still pending.
    eng._process = lambda batch: list(batch)
    futs = [eng.check_async(_req(i)) for i in range(5)]
    eng.close()
    for f in futs:
        resp = f.result(timeout=1)
        assert resp.error == ERR_ENGINE_DRAINING
        assert is_retryable_error(resp.error)


def test_engine_intake_after_close_fails_typed():
    """check_async/check_bulk on a closed engine resolve immediately
    with the typed retryable status instead of hanging."""
    eng = DeviceEngine(EngineConfig(num_groups=256, batch_size=128))
    eng.close()
    resp = eng.check_async(_req(0)).result(timeout=1)
    assert is_retryable_error(resp.error)
    out = eng.check_bulk([_req(1), _req(2)]).result(timeout=1)
    assert len(out) == 2 and all(is_retryable_error(r.error) for r in out)


@pytest.fixture(scope="module")
def daemon(loop_thread):
    c = loop_thread.run(Cluster.start(1, cache_size=4096), timeout=120)
    yield c.peer_at(0)
    # The drain tests close the daemon themselves; stop() tolerates a
    # second close (Daemon.close is idempotent).
    loop_thread.run(c.stop())


def test_readyz_and_healthcheck_distinguish_draining(daemon, loop_thread):
    """/readyz reports `draining` (503 with a distinct body) and
    cmd/healthcheck exits 2, so orchestrators stop routing without
    killing the pod early."""
    from gubernator_tpu.cmd.healthcheck import main as hc_main

    url = f"http://{daemon.http_address}"
    r = requests.get(f"{url}/readyz", timeout=5)
    assert r.status_code == 200

    daemon.svc.draining = True
    try:
        r = requests.get(f"{url}/readyz", timeout=5)
        assert r.status_code == 503
        assert r.json()["status"] == "draining"
        # HealthCheck body carries the drain state too.
        h = requests.get(f"{url}/v1/HealthCheck", timeout=5).json()
        assert h["status"] == "draining"
        assert hc_main(["--url", f"{url}/v1/HealthCheck"]) == 2
    finally:
        daemon.svc.draining = False
    assert hc_main(["--url", f"{url}/v1/HealthCheck"]) == 0


def test_daemon_drain_zero_failed_inflight(daemon, loop_thread):
    """The SIGTERM-drain acceptance: every request in flight when
    close() starts is answered (no errors, no hangs) — the gRPC grace
    covers the handlers and the engine pump drains its queue."""

    async def run():
        stub = daemon.client()
        from gubernator_tpu.service import pb

        async def one(i):
            msg = pb.pb.GetRateLimitsReq()
            msg.requests.append(
                pb.pb.RateLimitReq(
                    name="drain_inflight", unique_key=f"k{i}",
                    duration=600_000, limit=10_000, hits=1,
                )
            )
            resp = await stub.get_rate_limits(msg, timeout=30)
            return resp.responses[0]

        await one(10_000)  # prime: channel connected before the burst
        # "In flight" must mean HANDLER STARTED — RPCs still queued in
        # the server transport at stop() are refused (client-retryable),
        # not failed. Count handler entries and only close once all 80
        # are genuinely being served. (80 also stays under gRPC's ~100
        # concurrent-stream cap, so every call is admitted.)
        from gubernator_tpu.service import grpc_service

        started = 0
        orig_serve = grpc_service.serve_get_rate_limits_bytes

        async def counting_serve(svc, data, *call):
            nonlocal started
            started += 1
            return await orig_serve(svc, data, *call)

        grpc_service.serve_get_rate_limits_bytes = counting_serve
        try:
            tasks = [asyncio.ensure_future(one(i)) for i in range(80)]
            deadline = asyncio.get_running_loop().time() + 10
            while started < 80:
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.005)
            await daemon.close()
        finally:
            grpc_service.serve_get_rate_limits_bytes = orig_serve
        return await asyncio.gather(*tasks)

    results = loop_thread.run(run(), timeout=60)
    assert len(results) == 80
    failed = [r for r in results if r.error]
    assert not failed, f"{len(failed)} in-flight request(s) failed: {failed[:3]}"
    assert daemon.state == "stopped"


def test_forward_queue_sheds_with_typed_overload():
    """A full peer batch queue sheds producers with the typed overload
    error + counter instead of blocking them forever."""

    async def main():
        from gubernator_tpu.api.types import PeerInfo
        from gubernator_tpu.metrics import Metrics
        from gubernator_tpu.parallel.peers import Peer, PeerOverloadedError
        from gubernator_tpu.service.config import BehaviorConfig

        metrics = Metrics()
        peer = Peer(
            PeerInfo(grpc_address="10.0.0.1:81"),
            BehaviorConfig(),
            metrics=metrics,
        )
        # Stall the pump's RPC so the queue can only fill.
        blocked = asyncio.Event()

        async def stalled(reqs, timeout):
            await blocked.wait()
            return []

        peer._rpc_get_peer_rate_limits = stalled
        q = peer._ensure_pump()
        # Fill the queue directly to its bound.
        loop = asyncio.get_running_loop()
        while not q.full():
            q.put_nowait((_req(q.qsize()), loop.create_future()))
        with pytest.raises(PeerOverloadedError) as exc:
            await peer.get_peer_rate_limit(_req(99_999))
        assert is_retryable_error(str(exc.value))
        assert metrics.forward_queue_full.labels("queue_full").get() == 1
        blocked.set()
        await peer.shutdown()

    asyncio.run(main())
