"""Disaggregated serving edge: framed RPC between edge processes and
the device daemon.

TPU-native scale-out of the serving tier (SURVEY.md §2.3 sharding
row): the chip — and the one process
owning its HBM slot table — is the scarce resource, while gRPC
/ HTTP2 / TLS termination and the native wire parse are horizontally
scalable host work. N `gubernator-tpu-edge` processes terminate client
gRPC and relay each call over a length-prefixed stream (unix socket or
TCP, usually loopback) to the device daemon, which serves it through
the SAME core as its own gRPC listener
(grpc_service.serve_get_rate_limits_bytes: columnar fast path,
mixed-ownership splitting, object-path fallback) minus the gRPC server
cost. The reference scales by adding whole nodes to the peer mesh
(reference README.md:129-139); this splits a node into a device tier
and an edge tier instead — the edge speaks the identical V1 wire API,
so reference clients cannot tell the difference.

Frame format (little-endian):
    request:  u32 frame_len | u8 method | u64 call_id | payload
    response: u32 frame_len | u8 status | u64 call_id | payload
methods: 1 = V1/GetRateLimits (payload = GetRateLimitsReq bytes)
         2 = V1/HealthCheck   (payload ignored)
         3 = V1/Lease         (payload = lease request bytes, pb.py codec)
status:  0 = ok    (payload = response message bytes)
         1 = error (payload = u8 code_len | grpc-code-name | utf-8 message)
Responses are matched by call_id and may arrive out of order (the
listener serves frames concurrently; a slow mixed-ownership call does
not head-of-line-block a columnar one on the same connection).
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import struct
from typing import Optional, Tuple

log = logging.getLogger("gubernator_tpu.edge")

METHOD_GET_RATE_LIMITS = 1
METHOD_HEALTH_CHECK = 2
METHOD_LEASE = 3

_HDR = struct.Struct("<IBQ")  # frame_len (of method..payload) | method | call_id
MAX_FRAME = 8 << 20  # a 1000-item batch is ~100KB; 8MB is generous


class EdgeError(Exception):
    """Transported whole-call failure (grpc code name + message)."""

    def __init__(self, code: str, message: str):
        super().__init__(message)
        self.code = code


def _pack(method_or_status: int, call_id: int, payload: bytes) -> bytes:
    return _HDR.pack(9 + len(payload), method_or_status, call_id) + payload


async def _read_frame(reader) -> Optional[Tuple[int, int, bytes]]:
    """Returns (method_or_status, call_id, payload) or None on EOF."""
    try:
        hdr = await reader.readexactly(4)
        (flen,) = struct.unpack("<I", hdr)
        if flen < 9 or flen > MAX_FRAME:
            raise ValueError(f"bad frame length {flen}")
        body = await reader.readexactly(flen)
    except (asyncio.IncompleteReadError, ConnectionResetError):
        return None  # peer died mid-frame: same as EOF
    tag, call_id = struct.unpack("<BQ", body[:9])
    return tag, call_id, body[9:]


def _split_address(address: str) -> Tuple[bool, str, int]:
    """(is_unix, path_or_host, port). unix:///path, /path, or host:port."""
    if address.startswith("unix://"):
        return True, address[len("unix://"):], 0
    if address.startswith("/"):
        return True, address, 0
    host, port = address.rsplit(":", 1)
    return False, host.strip("[]"), int(port)


# ---- device-daemon side ----------------------------------------------------


class EdgeListener:
    """Accepts edge-process connections inside the device daemon and
    serves frames through the daemon's V1 core."""

    def __init__(self, svc, address: str):
        self.svc = svc
        self.address = address
        self._server: Optional[asyncio.AbstractServer] = None
        self._writers: set = set()

    async def start(self) -> None:
        is_unix, host, port = _split_address(self.address)
        if is_unix:
            # asyncio never removes the socket file; a stale one from a
            # previous daemon (clean exit or crash) would EADDRINUSE
            import contextlib
            import os

            with contextlib.suppress(OSError):
                os.unlink(host)
            self._server = await asyncio.start_unix_server(self._conn, path=host)
        else:
            self._server = await asyncio.start_server(self._conn, host, port)
        log.info("edge listener on %s", self.address)

    @property
    def bound_address(self) -> str:
        if self.address.startswith(("unix://", "/")):
            return self.address
        sock = self._server.sockets[0]
        host, port = sock.getsockname()[:2]
        return f"{host}:{port}"

    async def _conn(self, reader, writer) -> None:
        wlock = asyncio.Lock()  # frame writes must not interleave
        tasks = set()
        self._writers.add(writer)
        try:
            while True:
                frame = await _read_frame(reader)
                if frame is None:
                    break
                t = asyncio.ensure_future(self._serve(frame, writer, wlock))
                tasks.add(t)
                t.add_done_callback(tasks.discard)
        except (ValueError, ConnectionResetError) as e:
            log.warning("edge connection dropped: %s", e)
        finally:
            for t in tasks:
                t.cancel()
            self._writers.discard(writer)
            writer.close()

    async def _serve(self, frame, writer, wlock) -> None:
        from gubernator_tpu.service import pb
        from gubernator_tpu.service.grpc_service import (
            serve_get_rate_limits_bytes,
            serve_lease_bytes,
        )
        from gubernator_tpu.service.server import ApiError

        from gubernator_tpu.service.grpc_service import _instrumented

        method, call_id, payload = frame
        try:
            # Same instrumentation labels as the gRPC listener: in an
            # all-edge deployment the daemon's request count/duration
            # metrics must still see the traffic.
            if method == METHOD_GET_RATE_LIMITS:
                async with _instrumented(
                    self.svc.metrics, "/pb.gubernator.V1/GetRateLimits"
                ):
                    out = await serve_get_rate_limits_bytes(self.svc, payload)
            elif method == METHOD_LEASE:
                async with _instrumented(
                    self.svc.metrics, "/pb.gubernator.V1/Lease"
                ):
                    out = await serve_lease_bytes(self.svc, payload, None)
            elif method == METHOD_HEALTH_CHECK:
                async with _instrumented(
                    self.svc.metrics, "/pb.gubernator.V1/HealthCheck"
                ):
                    out = pb.health_to_pb(
                        await self.svc.health_check()
                    ).SerializeToString()
            else:
                raise ApiError(f"unknown edge method {method}", grpc_code="INTERNAL")
            resp = _pack(0, call_id, out)
        except ApiError as e:
            code = e.grpc_code.encode()
            resp = _pack(
                1, call_id, bytes([len(code)]) + code + str(e).encode()
            )
        except asyncio.CancelledError:
            raise
        # guberlint: allow-swallow -- the failure is serialized back to the edge client as an INTERNAL error frame
        except Exception as e:
            msg = f"edge serve failure: {e}".encode()
            resp = _pack(1, call_id, bytes([8]) + b"INTERNAL" + msg)
        try:
            async with wlock:
                writer.write(resp)
                await writer.drain()
        except (ConnectionResetError, RuntimeError):
            pass  # edge went away; its client sees the broken channel

    async def close(self) -> None:
        if self._server is not None:
            self._server.close()
        # server.close() only stops ACCEPTING; close live connections so
        # edges see EOF now (and so 3.12's wait_closed — which waits for
        # connection handlers — can finish)
        for w in list(self._writers):
            w.close()
        if self._server is not None:
            await self._server.wait_closed()


# ---- edge-process side -----------------------------------------------------


class EdgeClient:
    """Multiplexed client: N connections to the device daemon, calls
    matched to responses by call_id. Reconnects lazily on failure.

    `timeout_s` is the default per-call deadline (sourced from
    BehaviorConfig.edge_timeout_s / GUBER_EDGE_TIMEOUT by the edge
    entry point; it was a hard-coded 30.0). `timeout_counter` is any
    .inc()-able — timed-out calls bump it so edge-tier stalls are
    observable at the edge's /metrics.

    With `retries` > 0 (knob GUBER_EDGE_RETRIES at the edge entry
    point) UNAVAILABLE transport legs are re-sent under a token-bucket
    RetryBudget (service/overload.py, knob GUBER_RETRY_BUDGET): each
    first attempt deposits `retry_budget` tokens and each retry spends
    one, so an edge fleet's retry storm can amplify daemon load by at
    most 1 + retry_budget. `retries=0` (the constructor default) is
    the historical single-shot relay, bit-exact."""

    def __init__(
        self,
        address: str,
        connections: int = 2,
        timeout_s: float = 30.0,
        timeout_counter=None,
        retries: int = 0,
        retry_budget: float = 0.1,
    ):
        self.address = address
        self.timeout_s = timeout_s
        self.timeout_counter = timeout_counter
        self.retries = max(0, int(retries))
        self.retry_budget = None
        if self.retries > 0:
            from gubernator_tpu.service.overload import RetryBudget

            self.retry_budget = RetryBudget(ratio=retry_budget)
        self._n = max(1, connections)
        self._conns: list = [None] * self._n
        self._locks = [asyncio.Lock() for _ in range(self._n)]
        self._rr = itertools.count()
        self._ids = itertools.count(1)
        self._pending: dict = {}

    async def _connect(self, i: int):
        is_unix, host, port = _split_address(self.address)
        if is_unix:
            reader, writer = await asyncio.open_unix_connection(host)
        else:
            reader, writer = await asyncio.open_connection(host, port)
        conn = {"reader": reader, "writer": writer, "wlock": asyncio.Lock()}
        conn["pump"] = asyncio.ensure_future(self._pump(conn))
        self._conns[i] = conn
        return conn

    async def _pump(self, conn) -> None:
        try:
            while True:
                frame = await _read_frame(conn["reader"])
                if frame is None:
                    break
                status, call_id, payload = frame
                fut = self._pending.pop(call_id, None)
                if fut is not None and not fut.done():
                    fut.set_result((status, payload))
        except Exception as e:
            log.warning("edge upstream read failed: %s", e)
        finally:
            conn["dead"] = True
            # fail whatever was in flight on this connection
            for call_id in list(conn.get("calls", ())):
                fut = self._pending.pop(call_id, None)
                if fut is not None and not fut.done():
                    fut.set_exception(
                        EdgeError("UNAVAILABLE", "device daemon connection lost")
                    )

    async def call(
        self, method: int, payload: bytes, timeout: Optional[float] = None
    ) -> bytes:
        """One framed call, with budgeted UNAVAILABLE retries. Only
        transport-level UNAVAILABLE legs (daemon unreachable, pipe lost)
        re-send; DEADLINE_EXCEEDED and typed daemon errors propagate
        immediately — the daemon may already have applied the work."""
        budget = self.retry_budget
        if budget is not None:
            budget.record(1.0)
        attempt = 0
        while True:
            try:
                return await self._call_once(method, payload, timeout)
            except EdgeError as e:
                if (
                    e.code != "UNAVAILABLE"
                    or attempt >= self.retries
                    or budget is None
                    or not budget.try_spend()
                ):
                    raise
                attempt += 1
                await asyncio.sleep(min(0.025 * (2 ** attempt), 1.0))

    async def _call_once(
        self, method: int, payload: bytes, timeout: Optional[float] = None
    ) -> bytes:
        from gubernator_tpu.utils import faults

        if timeout is None:
            timeout = self.timeout_s
        if faults.active():
            try:
                await faults.inject(faults.EDGE_TARGET, faults.OP_EDGE_CALL)
            except faults.FaultInjected as e:
                raise EdgeError("UNAVAILABLE", str(e))
        i = next(self._rr) % self._n
        async with self._locks[i]:
            conn = self._conns[i]
            if conn is None or conn.get("dead"):
                try:
                    conn = await self._connect(i)
                except OSError as e:
                    raise EdgeError("UNAVAILABLE", f"device daemon unreachable: {e}")
        call_id = next(self._ids)
        fut = asyncio.get_running_loop().create_future()
        self._pending[call_id] = fut
        conn.setdefault("calls", set()).add(call_id)
        try:
            # Re-check AFTER registration: a pump that died in the gap
            # has already snapshotted conn["calls"] without this id, so
            # nobody would ever fail the future.
            if conn.get("dead"):
                raise EdgeError("UNAVAILABLE", "device daemon connection lost")
            async with conn["wlock"]:
                conn["writer"].write(_pack(method, call_id, payload))
                await conn["writer"].drain()
            status, resp = await asyncio.wait_for(fut, timeout)
        except EdgeError:
            raise
        except (OSError, ConnectionResetError) as e:
            conn["dead"] = True
            raise EdgeError("UNAVAILABLE", f"device daemon connection lost: {e}")
        except asyncio.TimeoutError:
            if self.timeout_counter is not None:
                self.timeout_counter.inc()
            raise EdgeError("DEADLINE_EXCEEDED", "device daemon call timed out")
        finally:
            # no-op on the happy path (the pump pops before resolving);
            # guarantees no leak on timeout/cancellation/errors
            self._pending.pop(call_id, None)
            conn.get("calls", set()).discard(call_id)
        if status == 0:
            return resp
        code_len = resp[0]
        code = resp[1 : 1 + code_len].decode("ascii", errors="replace")
        raise EdgeError(code, resp[1 + code_len :].decode("utf-8", errors="replace"))

    async def close(self) -> None:
        for conn in self._conns:
            if conn is not None:
                conn["pump"].cancel()
                conn["writer"].close()
        self._conns = [None] * self._n


class EdgeLeases:
    """Edge-tier lease holder: a LeaseCache plus the maintenance driver
    that reconciles it with the device daemon over METHOD_LEASE frames.

    Wired into EdgeV1Servicer / build_edge_app when GUBER_LEASES is on
    at the edge process; None (the default) keeps the edge a pure byte
    relay — bit-exact with today's wire behavior. Maintenance is lazy:
    each served call checks cache.due() and fires at most one
    background Lease RPC (renew at the low-water mark, returns for
    retired slices, grants for newly-wanted keys) — the cache's
    `inflight` flag is the only serialization needed because the edge
    process is single-loop. Maintenance frames ride EdgeClient.call,
    so when the edge runs with retries they share its RetryBudget —
    a flapping daemon pipe cannot turn lease upkeep into a retry
    storm."""

    def __init__(self, client: EdgeClient, cache, holder: str = "edge",
                 local_counter=None, recorder=None):
        self.client = client
        self.cache = cache
        self.holder = holder
        self.local_counter = local_counter
        # DecisionRecorder (service/admission.py): edge-answered debits
        # count under path=lease like holder-side daemon answers do.
        self.recorder = recorder
        self._tasks: set = set()

    def try_serve(self, req):
        resp = self.cache.try_serve(req)
        if resp is not None:
            if self.local_counter is not None:
                self.local_counter.inc()
            if self.recorder is not None:
                from gubernator_tpu.parallel.leases import (
                    LEASE_STALENESS_MD_KEY,
                )
                from gubernator_tpu.service.admission import PATH_LEASE

                self.recorder.record_decision(
                    PATH_LEASE,
                    resp,
                    key=req.hash_key(),
                    staleness_ms=int(
                        resp.metadata.get(LEASE_STALENESS_MD_KEY, 0)
                    ),
                )
        return resp

    def kick(self) -> None:
        if not self.cache.due():
            return
        t = asyncio.ensure_future(self.maintain())
        self._tasks.add(t)
        t.add_done_callback(self._tasks.discard)

    async def maintain(self) -> None:
        from gubernator_tpu.service import pb

        grants, returns = self.cache.collect()
        if not grants and not returns:
            self.cache.inflight = False
            return
        try:
            raw = await self.client.call(
                METHOD_LEASE,
                pb.lease_req_to_bytes(grants, returns, holder=self.holder),
            )
            g_res, _r_res, _md = pb.lease_resp_from_bytes(raw)
        except (EdgeError, ValueError, TypeError) as e:
            log.debug("edge lease maintenance failed: %s", e)
            self.cache.abort()
            return
        self.cache.apply(grants, g_res)

    async def close(self) -> None:
        """Best-effort final return of every held slice so the owner
        reclaims tokens as `returned` instead of waiting for expiry."""
        # A renewal in flight re-installs an entry on apply(); let it
        # land first so the final return covers every live slice.
        for t in list(self._tasks):
            try:
                await asyncio.wait_for(t, timeout=2.0)
            except (asyncio.TimeoutError, EdgeError):
                pass
        self.cache.drain_for_close()
        try:
            await asyncio.wait_for(self.maintain(), timeout=2.0)
        except (asyncio.TimeoutError, EdgeError):
            pass


async def _redispatch_sheds(
    client: EdgeClient, req_msg, raw_resp: bytes
) -> bytes:
    """One budgeted re-dispatch of per-item typed retryable errors (the
    daemon's overload governor refused those items without applying
    them — api.types.is_retryable_error), paced by the server's
    retry_after_ms response metadata. Active only when the EdgeClient
    has a RetryBudget (GUBER_EDGE_RETRIES > 0); the gate below is a
    bytes scan, so a shed-free response costs no protobuf parse."""
    from gubernator_tpu.api.types import RETRYABLE_PREFIX, is_retryable_error
    from gubernator_tpu.service import pb

    budget = client.retry_budget
    if budget is None or RETRYABLE_PREFIX.encode() not in raw_resp:
        return raw_resp
    try:
        resp = pb.pb.GetRateLimitsResp.FromString(raw_resp)
    except Exception:  # guberlint: allow-swallow -- a response we cannot parse relays verbatim; the client sees exactly what the daemon sent
        return raw_resp
    retry = [
        (i, m)
        for i, m in enumerate(resp.responses)
        if i < len(req_msg.requests) and is_retryable_error(m.error)
    ]
    if not retry or not budget.try_spend():
        return raw_resp
    delay = 0.05
    for _, m in retry:
        try:
            delay = max(delay, int(m.metadata.get("retry_after_ms", 0)) / 1000.0)
        except (TypeError, ValueError):
            pass
    await asyncio.sleep(min(delay, 5.0))
    sub = pb.pb.GetRateLimitsReq()
    for i, _ in retry:
        sub.requests.append(req_msg.requests[i])
    try:
        sub_resp = pb.pb.GetRateLimitsResp.FromString(
            await client.call(METHOD_GET_RATE_LIMITS, sub.SerializeToString())
        )
    except (EdgeError, ValueError):
        return raw_resp  # keep the original typed sheds; they are retryable
    for (i, _), m in zip(retry, sub_resp.responses):
        resp.responses[i].CopyFrom(m)
    return resp.SerializeToString()


async def serve_edge_get_rate_limits(
    client: EdgeClient, raw: bytes, leases: Optional[EdgeLeases] = None
) -> bytes:
    """GetRateLimits over the framed upstream, optionally through the
    edge lease cache: leased items are answered locally (zero frames to
    the daemon), only the misses are forwarded, and the responses are
    spliced back in request order. With `leases` None and no retry
    budget this is exactly the old one-line byte relay; with a budget
    (GUBER_EDGE_RETRIES) per-item overload sheds get one budgeted,
    retry_after_ms-paced re-dispatch before reaching the client."""
    if leases is None and client.retry_budget is None:
        return await client.call(METHOD_GET_RATE_LIMITS, raw)
    from gubernator_tpu.service import pb

    try:
        msg = pb.pb.GetRateLimitsReq.FromString(raw)
    except Exception:  # guberlint: allow-swallow -- unparseable payload relays verbatim so the daemon produces the same error a lease-less edge would
        return await client.call(METHOD_GET_RATE_LIMITS, raw)
    if leases is None:
        return await _redispatch_sheds(
            client, msg, await client.call(METHOD_GET_RATE_LIMITS, raw)
        )
    local = {}
    miss: list = []
    for i, m in enumerate(msg.requests):
        resp = leases.try_serve(pb.req_from_pb(m))
        if resp is not None:
            local[i] = resp
        else:
            miss.append(i)
    leases.kick()
    if not local:
        return await _redispatch_sheds(
            client, msg, await client.call(METHOD_GET_RATE_LIMITS, raw)
        )
    fwd_resps = []
    if miss:
        sub = pb.pb.GetRateLimitsReq()
        for i in miss:
            sub.requests.append(msg.requests[i])
        fwd_raw = await client.call(
            METHOD_GET_RATE_LIMITS, sub.SerializeToString()
        )
        fwd_resps = list(
            pb.pb.GetRateLimitsResp.FromString(fwd_raw).responses
        )
    out = pb.pb.GetRateLimitsResp()
    from gubernator_tpu.api.types import RateLimitResp

    fwd_it = iter(fwd_resps)
    for i in range(len(msg.requests)):
        if i in local:
            out.responses.append(pb.resp_to_pb(local[i]))
        else:
            nxt = next(fwd_it, None)
            if nxt is None:  # daemon returned fewer rows than sent
                out.responses.append(
                    pb.resp_to_pb(RateLimitResp(error="missing response"))
                )
            else:
                out.responses.append(nxt)
    return await _redispatch_sheds(client, msg, out.SerializeToString())


class EdgeV1Servicer:
    """grpc.aio servicer for the edge process: relays raw bytes.

    With `leases` (an EdgeLeases), GetRateLimits serves leased items
    from the local slice cache and relays only the misses."""

    def __init__(self, client: EdgeClient, leases: Optional[EdgeLeases] = None):
        self.client = client
        self.leases = leases

    async def GetRateLimits(self, request_bytes, context):
        import grpc

        try:
            return await serve_edge_get_rate_limits(
                self.client, request_bytes, self.leases
            )
        except EdgeError as e:
            await context.abort(
                getattr(grpc.StatusCode, e.code, grpc.StatusCode.INTERNAL), str(e)
            )

    async def HealthCheck(self, request_bytes, context):
        import grpc

        try:
            return await self.client.call(METHOD_HEALTH_CHECK, b"")
        except EdgeError as e:
            await context.abort(
                getattr(grpc.StatusCode, e.code, grpc.StatusCode.INTERNAL), str(e)
            )

    async def Lease(self, request_bytes, context):
        """Relay client-SDK Lease calls: holders behind an edge lease
        from the daemon exactly as holders dialing it directly."""
        import grpc

        try:
            return await self.client.call(METHOD_LEASE, request_bytes)
        except EdgeError as e:
            await context.abort(
                getattr(grpc.StatusCode, e.code, grpc.StatusCode.INTERNAL), str(e)
            )


_EDGE_HTTP_CODES = {
    "INVALID_ARGUMENT": 400,
    "OUT_OF_RANGE": 400,
    "UNAVAILABLE": 503,
    "DEADLINE_EXCEEDED": 504,
}
_EDGE_JSON_CODES = {  # gRPC status numbers for the JSON error body
    "INVALID_ARGUMENT": 3,
    "DEADLINE_EXCEEDED": 4,
    "OUT_OF_RANGE": 11,
    "INTERNAL": 13,
    "UNAVAILABLE": 14,
}


def build_edge_app(client: EdgeClient, metrics=None, leases=None):
    """aiohttp app mirroring the daemon's HTTP/JSON gateway
    (service/gateway.py) over the framed upstream — the edge presents
    the daemon's full client-facing surface (gRPC + JSON + /healthz).
    With `metrics` (a gubernator_tpu.metrics.Metrics), the edge also
    serves its own /metrics — edge-local series like
    gubernator_edge_call_timeouts live here, not on the daemon. With
    `leases` (an EdgeLeases) the JSON path shares the gRPC path's
    local lease serving."""
    from aiohttp import web

    from gubernator_tpu.service import pb
    from gubernator_tpu.service.gateway import read_json_requests

    app = web.Application()

    def _edge_err(e: EdgeError) -> web.Response:
        return web.json_response(
            {"code": _EDGE_JSON_CODES.get(e.code, 13), "message": str(e)},
            status=_EDGE_HTTP_CODES.get(e.code, 500),
        )

    async def get_rate_limits(request: web.Request) -> web.Response:
        reqs, err = await read_json_requests(request)
        if err is not None:
            return err
        msg = pb.pb.GetRateLimitsReq()
        for r in reqs:
            msg.requests.append(pb.req_to_pb(r))
        try:
            raw = await serve_edge_get_rate_limits(
                client, msg.SerializeToString(), leases
            )
        except EdgeError as e:
            return _edge_err(e)
        out = pb.pb.GetRateLimitsResp.FromString(raw)
        return web.json_response(
            {
                "responses": [
                    pb.resp_to_json(pb.resp_from_pb(m)) for m in out.responses
                ]
            }
        )

    async def _health():
        raw = await client.call(METHOD_HEALTH_CHECK, b"")
        return pb.pb.HealthCheckResp.FromString(raw)

    async def health_check(request: web.Request) -> web.Response:
        try:
            h = await _health()
        except EdgeError as e:
            return _edge_err(e)
        # same body shape as the daemon gateway (pb.health_to_json):
        # message omitted when empty
        body = {"status": h.status, "peer_count": h.peer_count}
        if h.message:
            body["message"] = h.message
        return web.json_response(body)

    async def healthz(request: web.Request) -> web.Response:
        try:
            h = await _health()
        except EdgeError:
            return web.Response(text="unreachable", status=503)
        return web.Response(
            text=h.status, status=200 if h.status == "healthy" else 503
        )

    app.router.add_post("/v1/GetRateLimits", get_rate_limits)
    app.router.add_get("/v1/HealthCheck", health_check)
    app.router.add_get("/healthz", healthz)
    if metrics is not None:

        async def metrics_route(request: web.Request) -> web.Response:
            return web.Response(
                body=metrics.render(), content_type="text/plain", charset="utf-8"
            )

        app.router.add_get("/metrics", metrics_route)
    return app


def edge_v1_handler(servicer) -> "grpc.GenericRpcHandler":  # noqa: F821
    """V1 service handler with identity (de)serializers on BOTH methods
    — the edge never parses messages, it relays bytes."""
    import grpc

    return grpc.method_handlers_generic_handler(
        "pb.gubernator.V1",
        {
            "GetRateLimits": grpc.unary_unary_rpc_method_handler(
                servicer.GetRateLimits,
                request_deserializer=None,
                response_serializer=None,
            ),
            "HealthCheck": grpc.unary_unary_rpc_method_handler(
                servicer.HealthCheck,
                request_deserializer=None,
                response_serializer=None,
            ),
            "Lease": grpc.unary_unary_rpc_method_handler(
                servicer.Lease,
                request_deserializer=None,
                response_serializer=None,
            ),
        },
    )
