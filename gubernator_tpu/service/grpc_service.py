"""grpc.aio servicers bridging the wire to V1Service."""

from __future__ import annotations

import asyncio
import contextlib
import time

import grpc

from gubernator_tpu.service import pb
from gubernator_tpu.service.server import ApiError, V1Service
from gubernator_tpu.utils import tracing

_GRPC_CODES = {
    "OUT_OF_RANGE": grpc.StatusCode.OUT_OF_RANGE,
    "INVALID_ARGUMENT": grpc.StatusCode.INVALID_ARGUMENT,
    "INTERNAL": grpc.StatusCode.INTERNAL,
}


@contextlib.asynccontextmanager
async def _instrumented(metrics, method: str, call=None):
    """Per-RPC duration + success/failed counters (the reference's
    GRPCStatsHandler role, grpc_stats.go:41-131). Counts every outcome:
    any exception — ApiError-driven aborts included — is 'failed'.

    `call` (a tracing.CallRecord, GetRateLimits / GetPeerRateLimits) is
    the call's timeline: its root runs from the clock read that opens
    the duration to the one that closes it, so its stages add up to
    the duration observed here; the request span `rpc.<Method>` (INFO)
    covers the columnar and the object path alike."""
    t0 = time.perf_counter_ns()
    root = None
    if call is not None:
        call.begin(t0)
        tracing.rpc_mark("rpc.begin", call.ids)
        root = tracing.start_span(
            "rpc." + method.rpartition("/")[2], level="INFO", call=call.seq
        )
        call.otel_ctx = tracing.context_of(root)
    err = None
    try:
        if root is None:  # no SDK: nothing to make current
            yield
        else:
            with tracing.use_span_ctx(root):
                yield
        metrics.grpc_request_counts.labels(method, "success").inc()
    except BaseException as e:
        err = e
        metrics.grpc_request_counts.labels(method, "failed").inc()
        raise
    finally:
        t1 = time.perf_counter_ns()
        metrics.grpc_request_duration.labels(method).observe((t1 - t0) * 1e-9)
        if call is not None:
            metrics.edge_calls.labels(call.finish(t1), call.reason).inc()
            tracing.rpc_mark("rpc.end", call.ids)
            tracing.end_span(root, error=err)


async def _abort(context, e: ApiError):
    await context.abort(_GRPC_CODES.get(e.grpc_code, grpc.StatusCode.INTERNAL), str(e))


async def serve_get_rate_limits_bytes(
    svc: V1Service, request_bytes, call=tracing.NO_CALL
) -> bytes:
    """The V1/GetRateLimits serving core over raw wire bytes, shared by
    the gRPC servicer and the edge-tier listener (service/edge.py) so
    both transports have identical semantics. Raises ApiError for
    whole-call failures (the caller maps it to its transport's status).
    `call` is the gRPC handler's timeline (tracing.CallRecord)."""
    from gubernator_tpu.service import fastpath

    if fastpath.enabled(svc):
        # Executor keeps the event loop responsive while the
        # kernel runs (the C parse and the jitted decide release
        # the GIL, so calls genuinely overlap).
        res = await asyncio.get_running_loop().run_in_executor(
            None, fastpath.try_serve, svc, request_bytes, False, call
        )
        if isinstance(res, bytes):
            call.mark("loop_return")
            return res
        if res is not None:  # mixed ownership: forward the rest
            call.mark("loop_return")
            _, n, local_pos, local_out, nl_reqs, md = res
            # Local hits are already committed — a forwarding
            # failure must degrade the REMOTE items to per-item
            # errors, never fail the RPC (a client retry would
            # double-charge every local key).
            from gubernator_tpu.api.types import RateLimitResp

            try:
                nl_resps = await svc.get_rate_limits(nl_reqs, call=call)
            except Exception as e:
                nl_resps = [RateLimitResp(error=str(e)) for _ in nl_reqs]
            with tracing.stage("call.build", call, call.ids):
                return fastpath.merge_mixed(
                    n, local_pos, local_out, nl_resps, md
                )
        call.attempt_refused()
    with tracing.stage("call.pb_decode", call, call.ids):
        try:
            request = pb.pb.GetRateLimitsReq.FromString(request_bytes)
        except Exception:
            raise ApiError("malformed request", grpc_code="INVALID_ARGUMENT")
        reqs = [pb.req_from_pb(r) for r in request.requests]
    out = await svc.get_rate_limits(reqs, call=call)
    with tracing.stage("call.pb_encode", call, call.ids):
        resp = pb.pb.GetRateLimitsResp()
        for r in out:
            resp.responses.append(pb.resp_to_pb(r))
        return resp.SerializeToString()


async def serve_lease_bytes(svc: V1Service, request_bytes, context) -> bytes:
    """Shared Lease serving core (V1 + PeersV1 + the edge framed
    listener): decode, route through V1Service.lease, encode."""
    from gubernator_tpu.utils import tracing

    try:
        grants, returns, holder, md = pb.lease_req_from_bytes(request_bytes)
    except (ValueError, TypeError):
        if context is not None:
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT, "malformed lease request"
            )
        raise ApiError("malformed lease request")
    ctx = tracing.propagate_extract(md)
    with tracing.attached(ctx):
        with tracing.span(
            "V1Instance.Lease", level="DEBUG",
            grants=len(grants), returns=len(returns),
        ):
            g_res, r_res = await svc.lease(
                grants, returns, holder=holder,
                no_forward=md.get("no_forward") == "1",
            )
    return pb.lease_resp_to_bytes(g_res, r_res)


class V1Servicer:
    """GetRateLimits runs in BYTES mode (identity deserializer): the
    columnar fast path serves eligible calls without building a single
    per-item Python object; everything else parses and takes the object
    path with identical semantics (service/fastpath.py)."""

    def __init__(self, svc: V1Service):
        self.svc = svc

    async def GetRateLimits(self, request_bytes, context):
        m = self.svc.metrics
        call = tracing.CallRecord(m.call_stages)
        async with _instrumented(m, "/pb.gubernator.V1/GetRateLimits", call):
            try:
                return await serve_get_rate_limits_bytes(
                    self.svc, request_bytes, call
                )
            except ApiError as e:
                await _abort(context, e)

    async def HealthCheck(self, request, context):
        async with _instrumented(self.svc.metrics, "/pb.gubernator.V1/HealthCheck"):
            return pb.health_to_pb(await self.svc.health_check())

    async def Lease(self, request_bytes, context):
        """Cooperative token leases (docs/architecture.md): grant/renew/
        return quota slices. The service routes each row to the owning
        daemon — local grants hit the LeaseManager, remote ones forward
        over PeersV1/Lease."""
        async with _instrumented(self.svc.metrics, "/pb.gubernator.V1/Lease"):
            return await serve_lease_bytes(self.svc, request_bytes, context)


class PeersV1Servicer:
    def __init__(self, svc: V1Service):
        self.svc = svc
        from gubernator_tpu.service import fastpath

        self._fast = fastpath

    async def GetPeerRateLimits(self, request_bytes, context):
        m = self.svc.metrics
        call = tracing.CallRecord(m.call_stages, kind="peer_")
        async with _instrumented(
            m, "/pb.gubernator.PeersV1/GetPeerRateLimits", call
        ):
            # Forwarded batches are owned by construction — the owner-side
            # hot path (SURVEY.md §3.2) skips the ring check. The response
            # field (rate_limits = 1) shares its wire shape with
            # GetRateLimitsResp.responses, so the same native builder
            # serves both.
            if self._fast.enabled(self.svc):
                raw = await asyncio.get_running_loop().run_in_executor(
                    None, self._fast.try_serve, self.svc, request_bytes,
                    True, call,
                )
                if isinstance(raw, bytes):  # peer calls are never "mixed"
                    call.mark("loop_return")
                    return raw
                call.attempt_refused()
            reqs = None
            with tracing.stage("call.pb_decode", call, call.ids):
                try:
                    request = pb.peers_pb.GetPeerRateLimitsReq.FromString(
                        request_bytes
                    )
                # guberlint: allow-swallow -- not swallowed: reqs stays None and the call is aborted INVALID_ARGUMENT right below, outside the stage (a stage's body holds no await)
                except Exception:
                    pass
                else:
                    reqs = [pb.req_from_pb(r) for r in request.requests]
            if reqs is None:
                await context.abort(
                    grpc.StatusCode.INVALID_ARGUMENT, "malformed request"
                )
            try:
                out = await self.svc.get_peer_rate_limits(reqs, call=call)
            except ApiError as e:
                await _abort(context, e)
            with tracing.stage("call.pb_encode", call, call.ids):
                resp = pb.peers_pb.GetPeerRateLimitsResp()
                for r in out:
                    resp.rate_limits.append(pb.resp_to_pb(r))
                return resp.SerializeToString()

    async def UpdatePeerGlobals(self, request, context):
        async with _instrumented(
            self.svc.metrics, "/pb.gubernator.PeersV1/UpdatePeerGlobals"
        ):
            await self.svc.update_peer_globals(
                [pb.global_from_pb(g) for g in request.globals]
            )
            return pb.peers_pb.UpdatePeerGlobalsResp()

    async def TransferSnapshots(self, request_bytes, context):
        """Ownership handover receiver (docs/robustness.md): merge the
        sender's counter state last-writer-wins on stamp. The chunk's
        optional metadata carries the sender's trace context, so the
        receive + merge lands under the sender's handover trace."""
        from gubernator_tpu.utils import tracing

        async with _instrumented(
            self.svc.metrics, "/pb.gubernator.PeersV1/TransferSnapshots"
        ):
            # Standby envelope (v=2, parallel/standby.py) rides the same
            # RPC: route it to the shadow store when this node runs a
            # ReplicationManager; reject it INVALID_ARGUMENT otherwise —
            # the SAME rejection class a pre-standby build produces, so
            # skewed senders fall back to v=1 full images either way.
            try:
                parsed = pb.maybe_standby_from_bytes(request_bytes)
            except ValueError as e:
                await context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
            if parsed is not None:
                sb = getattr(self.svc, "standby", None)
                if sb is None:
                    await context.abort(
                        grpc.StatusCode.INVALID_ARGUMENT,
                        "standby replication not enabled on this node",
                    )
                loop = asyncio.get_running_loop()
                accepted, stale, extra = await loop.run_in_executor(
                    None, sb.receive, parsed
                )
                return pb.transfer_resp_to_bytes(accepted, stale, extra)
            try:
                snaps, md, leases = pb.snapshots_full_from_bytes(
                    request_bytes
                )
            except (ValueError, TypeError):
                await context.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    "malformed snapshot transfer",
                )
            ctx = tracing.propagate_extract(md)
            with tracing.attached(ctx):
                with tracing.span(
                    "PeersV1.TransferSnapshots", level="DEBUG",
                    keys=len(snaps),
                ):
                    accepted, stale = await self.svc.transfer_snapshots(
                        snaps, leases=leases
                    )
            return pb.transfer_resp_to_bytes(accepted, stale)

    async def DebugInfo(self, request_bytes, context):
        """Consistency observatory: serve this node's debug blob — LOCAL
        state only, so the /debug/cluster fan-out cannot recurse. With
        `keys`, includes those keys' counter snapshots (the divergence
        auditor's replica-view fetch)."""
        from gubernator_tpu.utils import tracing

        async with _instrumented(
            self.svc.metrics, "/pb.gubernator.PeersV1/DebugInfo"
        ):
            try:
                keys, md = pb.debug_req_from_bytes(request_bytes)
            except (ValueError, TypeError):
                await context.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    "malformed debug info request",
                )
            ctx = tracing.propagate_extract(md)
            with tracing.attached(ctx):
                with tracing.span(
                    "PeersV1.DebugInfo", level="DEBUG", keys=len(keys)
                ):
                    # Engine readbacks + table snapshot off the loop.
                    info = await asyncio.get_running_loop().run_in_executor(
                        None, self.svc.local_debug_info, keys or None
                    )
            return pb.debug_resp_to_bytes(info)

    async def Lease(self, request_bytes, context):
        """Daemon-to-owner forwarded lease traffic: same payload and
        serving core as V1/Lease (the service refuses to re-forward a
        peer-forwarded request — `no_forward` rides the payload md — so
        disagreeing ring views cannot loop)."""
        async with _instrumented(
            self.svc.metrics, "/pb.gubernator.PeersV1/Lease"
        ):
            return await serve_lease_bytes(self.svc, request_bytes, context)
