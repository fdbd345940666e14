"""HTTP/JSON gateway + /metrics + /healthz + /debug.

Mirrors the reference's grpc-gateway mux (reference daemon.go:251-299):
POST /v1/GetRateLimits and GET /v1/HealthCheck speak snake_case JSON
(pinned by the reference's TestGRPCGateway), /metrics serves Prometheus
text, /healthz is the liveness probe.

Device-tier debug surface (docs/monitoring.md; no reference analog):

- GET /debug/engine — the engine's flight recorder (last K flush
  records), histogram summaries, counters, and table occupancy as JSON.
- GET /debug/hotkeys — top-K hot-key attribution (the space-saving
  sketch: estimated hits, error bound, over-limit counts per key).
- GET /debug/profile?seconds=N — on-demand jax.profiler capture to a
  temp dir (one capture at a time process-wide; 503 when busy or when
  the profiler is unavailable). Works on CPU too — the XLA profiler is
  backend-agnostic. A capture whose stop outlasts its window by more
  than ten seconds answers 200 at once and keeps the connection alive
  with newlines until the JSON follows. The capture holds the
  program's own rpc.*, call.* and flush.* spans in plane /host:CPU;
  `python=1` adds Python frames (and slows the server it traces).
- GET /debug/slo — the SLO observatory: per-SLO multi-window burn
  rates, alert states, remaining error budgets, and the self-watchdog's
  per-loop heartbeat table (docs/monitoring.md "SLOs & burn rates").

Both are served by the main gateway AND the status listener
(daemon.go:305-333 analog), so an mTLS deployment can reach them
without client certs.
"""

from __future__ import annotations

import asyncio
import functools
import json
import logging

from aiohttp import web

from gubernator_tpu.service import pb
from gubernator_tpu.service import profiler as _profiler
from gubernator_tpu.service.server import ApiError, V1Service

log = logging.getLogger("gubernator_tpu.gateway")

# jax.profiler state is process-global: exactly one capture at a time,
# regardless of how many daemons/listeners share the process. The guard
# and the bounded/rotating capture itself live in service/profiler.py
# (shared with the continuous sampler); these aliases keep the
# historical gateway names importable.
_PROFILE_GUARD = _profiler.PROFILE_GUARD
_PROFILE_MAX_SECONDS = _profiler.PROFILE_MAX_SECONDS


# A capture still running this long after its window gets a reply that
# is kept alive (debug_profile).
_PROFILE_HEARTBEAT_S = 10.0


def _capture_ended(fut) -> None:
    if not fut.cancelled():
        fut.exception()  # retrieved here if the request is gone
    _PROFILE_GUARD.release()


def _capture_reply(fut) -> tuple:
    """(JSON body, status) of a finished capture."""
    try:
        return fut.result(), 200
    except Exception as e:
        log.warning("profile capture failed: %s", e)
        return {"error": f"profiler unavailable: {e}"}, 503


def add_debug_routes(app: web.Application, svc: V1Service) -> None:
    async def debug_engine(request: web.Request) -> web.Response:
        # debug_snapshot takes the engine lock for an occupancy readback;
        # keep it off the event loop.
        snap = await asyncio.get_running_loop().run_in_executor(
            None, svc.engine.debug_snapshot
        )
        return web.json_response(snap)

    async def debug_profile(request: web.Request) -> web.Response:
        try:
            seconds = float(request.query.get("seconds", "1"))
        except ValueError:
            return web.json_response(
                {"error": "seconds must be a number"}, status=400
            )
        seconds = min(max(seconds, 0.05), _PROFILE_MAX_SECONDS)
        if not _PROFILE_GUARD.acquire(blocking=False):
            # Captures are short and serialized; tell pollers when to
            # come back instead of having them hammer the 503.
            return web.json_response(
                {"error": "a profile capture is already running"},
                status=503,
                headers={"Retry-After": str(int(seconds) or 1)},
            )
        python = request.query.get("python", "0") in ("1", "true")
        fut = asyncio.get_running_loop().run_in_executor(
            None, functools.partial(_profiler.capture, seconds, python=python)
        )
        # A capture cannot be cancelled: the guard goes back when its
        # thread ends, whatever becomes of this request.
        fut.add_done_callback(_capture_ended)
        done, _ = await asyncio.wait(
            {fut}, timeout=seconds + _PROFILE_HEARTBEAT_S
        )
        if done:
            out, status = _capture_reply(fut)
            return web.json_response(out, status=status)
        # stop_trace outlives the capture by a minute and more on a
        # loaded server (it decodes ~900 device events a decide launch,
        # PERF.md §6): a reply that stayed silent that long runs into
        # the idle timeout of the client or of a proxy between. Start
        # the reply and send a newline, which JSON allows before a
        # value, every few seconds until the trace is on disk.
        resp = web.StreamResponse(headers={"Content-Type": "application/json"})
        await resp.prepare(request)
        while not done:
            await resp.write(b"\n")
            done, _ = await asyncio.wait({fut}, timeout=_PROFILE_HEARTBEAT_S)
        await resp.write(json.dumps(_capture_reply(fut)[0]).encode())
        await resp.write_eof()
        return resp

    async def debug_device(request: web.Request) -> web.Response:
        """Device-resource observatory (docs/monitoring.md "Device
        resources"): per-subsystem HBM attribution + headroom, the
        host<->device transfer ledger, and compile telemetry with
        retrace attribution. Pure host-side reads (one allocator stats
        query, histogram summaries, bounded ring copies) — no device
        program runs (GL009); executor only for the engine attribute
        reads."""
        snap = await asyncio.get_running_loop().run_in_executor(
            None, svc.device_debug_info
        )
        return web.json_response(snap)

    async def debug_hotkeys(request: web.Request) -> web.Response:
        # Sketch snapshot + census residency join: the join gathers the
        # tracked keys' slot rows under the engine lock — executor, not
        # event loop.
        snap = await asyncio.get_running_loop().run_in_executor(
            None, svc.engine.hotkeys_snapshot
        )
        return web.json_response(snap)

    async def debug_table(request: web.Request) -> web.Response:
        """Full table-census snapshot (docs/monitoring.md "Table
        census"): per-tier age/idle histograms, the group-region
        occupancy heatmap, waste + cold-set summaries, and the churn
        ledger. TTL-cached in the engine — scraping this endpoint never
        triggers device work beyond one census per TTL interval; the
        cache read still briefly takes engine locks, so executor."""
        snap = await asyncio.get_running_loop().run_in_executor(
            None, svc.engine.table_census
        )
        return web.json_response(snap)

    async def debug_leases(request: web.Request) -> web.Response:
        """Owner-side lease ledger (docs/architecture.md "Cooperative
        leases"): record/key counts, granted/returned/expired/credited
        hit flows, the outstanding over-admission bound, revocation
        state, and the top outstanding keys. Pure host-side dict reads;
        {"enabled": false} when GUBER_LEASES is off."""
        if svc.lease_mgr is None:
            return web.json_response({"enabled": False})
        return web.json_response(
            {"enabled": True, **svc.lease_mgr.summary()}
        )

    async def debug_admission(request: web.Request) -> web.Response:
        """Admission observatory (docs/monitoring.md "Admission"): the
        engine's TTL-cached ground-truth window accounting (admitted vs
        configured limit over the resident table), decision counts by
        serving path, the node's over-admission bound (outstanding lease
        hits + un-relayed GLOBAL hits), and the decision flight-recorder
        ring. TTL-cached engine snapshot + host dict copies — scraping
        this endpoint never compiles or dispatches device work beyond
        one scan per TTL interval; the cache read takes engine locks,
        so executor."""
        snap = await asyncio.get_running_loop().run_in_executor(
            None, svc.admission_debug_info
        )
        return web.json_response(snap)

    async def debug_slo(request: web.Request) -> web.Response:
        """SLO observatory (docs/monitoring.md "SLOs & burn rates"):
        per-SLO multi-window burn rates, alert states (ok / slow_burn /
        fast_burn / exhausted), remaining error budgets, the sampled
        SLI time-series summaries, and the self-watchdog's per-loop
        heartbeat table. Pure host-side ring arithmetic over values the
        background sampler already cached — scraping this endpoint does
        zero device work; the ring reads take per-ring locks, so
        executor. {"enabled": false} when the observatory isn't wired."""
        snap = await asyncio.get_running_loop().run_in_executor(
            None, svc.slo_debug_info
        )
        return web.json_response(snap)

    async def debug_standby(request: web.Request) -> web.Response:
        """Crash-tolerance observatory (docs/robustness.md "Standby
        replication & crash recovery"): the published hard-kill loss
        bound, pending (unacked) ledger size, shadow inventory by
        source owner, promotion history, and legacy (v1-fallback)
        peers. Host-side dict copies plus one dirty-registry read under
        its own lock — zero device work (GL009); executor because the
        loss bound briefly takes that lock. {"enabled": false} when
        GUBER_STANDBY is off."""
        snap = await asyncio.get_running_loop().run_in_executor(
            None, svc.standby_debug_info
        )
        return web.json_response(snap)

    async def debug_overload(request: web.Request) -> web.Response:
        """Overload control plane (docs/robustness.md "Overload
        control & brownout"): the brownout ladder level + the signals
        driving it, and the intake governor's controller state — shed
        counts by reason, CoDel standing-queue state, per-tenant shed
        weights and heavy-hitter attribution. Host-side dict copies
        under the governor's own lock — zero device work (GL009);
        executor for the lock. {"enabled": false} when GUBER_OVERLOAD
        is off."""
        snap = await asyncio.get_running_loop().run_in_executor(
            None, svc.overload_debug_info
        )
        return web.json_response(snap)

    async def debug_cluster(request: web.Request) -> web.Response:
        """Cluster-wide debug view (docs/monitoring.md "Consistency"):
        this node's local_debug_info plus a breaker-gated, shared-deadline
        fan-out of PeersV1.DebugInfo to every live peer — the whole mesh's
        health, breakers, occupancy, hot keys, and consistency gauges
        from any single node. Skipped (circuit open) and failed peers
        appear as {"error": ...} rows, never as a whole-call failure."""
        loop = asyncio.get_running_loop()
        local = await loop.run_in_executor(None, svc.local_debug_info)
        out = {"local": local, "peers": {}}
        peers = []
        if svc.picker is not None:
            peers = [p for p in svc.picker.peers() if not p.info.is_owner]
        if peers:
            budget_s = 2.0
            if svc.forwarder is not None:
                budget_s = float(
                    getattr(svc.forwarder.behaviors, "forward_deadline_s", 2.0)
                )
            deadline = loop.time() + budget_s

            async def fetch(peer):
                addr = peer.info.grpc_address
                if not peer.breaker.allow():
                    return addr, {"error": "circuit open"}
                remaining = deadline - loop.time()
                if remaining <= 0:
                    return addr, {"error": "deadline exceeded"}
                try:
                    return addr, await peer.debug_info(timeout=remaining)
                except Exception as e:  # guberlint: allow-swallow -- failure becomes this peer's {"error": ...} row; the peer leg already counted it
                    return addr, {"error": str(e)}

            for addr, blob in await asyncio.gather(*(fetch(p) for p in peers)):
                out["peers"][addr] = blob
        return web.json_response(out)

    app.router.add_get("/debug/engine", debug_engine)
    app.router.add_get("/debug/hotkeys", debug_hotkeys)
    app.router.add_get("/debug/table", debug_table)
    app.router.add_get("/debug/device", debug_device)
    app.router.add_get("/debug/profile", debug_profile)
    app.router.add_get("/debug/leases", debug_leases)
    app.router.add_get("/debug/admission", debug_admission)
    app.router.add_get("/debug/slo", debug_slo)
    app.router.add_get("/debug/standby", debug_standby)
    app.router.add_get("/debug/overload", debug_overload)
    app.router.add_get("/debug/cluster", debug_cluster)


def add_probe_routes(app: web.Application, svc: V1Service) -> None:
    """/livez + /readyz (docs/robustness.md). /healthz keeps the
    reference's TTL'd-error semantics for back-compat, but it conflates
    liveness with mesh health: one flapping peer 503s the node for the
    full 5-minute error TTL, so a restart-on-liveness orchestrator
    would bounce a healthy process. The split:

    - /livez: process liveness only — 200 while the event loop serves.
    - /readyz: breaker-derived readiness — 200 "ready" (all circuits
      closed), 200 "degraded" (some open; surviving keys still serve),
      503 "unready" (every peer circuit open), 503 "draining" (graceful
      shutdown: stop routing, don't kill — the body distinguishes it
      from "unready" so orchestrators and cmd/healthcheck.py can tell
      a leaving node from a partitioned one). Flips degraded -> ready
      without a restart the moment a returning peer's circuit closes.
    """

    async def livez(request: web.Request) -> web.Response:
        return web.Response(text="ok")

    async def readyz(request: web.Request) -> web.Response:
        r = svc.readiness()
        return web.json_response(
            r, status=503 if r["status"] in ("unready", "draining") else 200
        )

    app.router.add_get("/livez", livez)
    app.router.add_get("/readyz", readyz)


async def read_json_requests(request: web.Request):
    """Parse + validate a /v1/GetRateLimits JSON body.

    Returns (reqs, None) or (None, error_response). Shared by the
    daemon gateway and the edge gateway (service/edge.py) so the two
    HTTP fronts cannot diverge on the wire contract."""
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return None, web.json_response(
            {"code": 3, "message": f"invalid JSON: {e}"}, status=400
        )
    if not isinstance(body, dict):
        return None, web.json_response(
            {"code": 3, "message": "request body must be a JSON object"},
            status=400,
        )
    items = body.get("requests") or []
    if not isinstance(items, list) or not all(
        isinstance(d, dict) for d in items
    ):
        return None, web.json_response(
            {"code": 3, "message": "'requests' must be a list of objects"},
            status=400,
        )
    try:
        return [pb.req_from_json(d) for d in items], None
    except (TypeError, ValueError) as e:
        return None, web.json_response(
            {"code": 3, "message": f"invalid request: {e}"}, status=400
        )


def build_app(svc: V1Service) -> web.Application:
    app = web.Application()

    async def get_rate_limits(request: web.Request) -> web.Response:
        reqs, err = await read_json_requests(request)
        if err is not None:
            return err
        try:
            out = await svc.get_rate_limits(reqs)
        except ApiError as e:
            return web.json_response({"code": 11, "message": str(e)}, status=e.http_code)
        return web.json_response({"responses": [pb.resp_to_json(r) for r in out]})

    async def health_check(request: web.Request) -> web.Response:
        h = await svc.health_check()
        return web.json_response(pb.health_to_json(h))

    async def healthz(request: web.Request) -> web.Response:
        h = await svc.health_check()
        return web.Response(
            text=h.status, status=200 if h.status == "healthy" else 503
        )

    async def metrics(request: web.Request) -> web.Response:
        # OpenMetrics content negotiation: exemplars (trace ids on
        # histogram buckets) render ONLY when the scraper asks for
        # application/openmetrics-text; plain scrapes stay byte-stable.
        body, ctype = svc.metrics.render_negotiated(
            request.headers.get("Accept", "")
        )
        return web.Response(body=body, headers={"Content-Type": ctype})

    app.router.add_post("/v1/GetRateLimits", get_rate_limits)
    app.router.add_get("/v1/HealthCheck", health_check)
    app.router.add_get("/healthz", healthz)
    app.router.add_get("/metrics", metrics)
    add_probe_routes(app, svc)
    add_debug_routes(app, svc)
    return app


def build_status_app(svc: V1Service) -> web.Application:
    """Health + debug app for the no-mTLS status listener (reference
    daemon.go:305-333 serves /v1/HealthCheck there; the device-tier
    debug surface rides the same listener so operators can reach the
    flight recorder and profiler without client certs)."""
    app = web.Application()

    async def health_check(request: web.Request) -> web.Response:
        h = await svc.health_check()
        return web.json_response(pb.health_to_json(h))

    app.router.add_get("/v1/HealthCheck", health_check)
    add_probe_routes(app, svc)
    add_debug_routes(app, svc)
    return app
