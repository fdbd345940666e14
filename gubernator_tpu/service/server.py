"""Core rate-limit service: validation, ownership routing, execution.

The transport-agnostic heart of the daemon (the reference's V1Instance,
gubernator.go:45-773): gRPC servicers and the HTTP gateway both call into
this class. Owner-path items go to the local DeviceEngine in one batch;
non-owner items are forwarded to the owning peer (micro-batched by
PeerForwarder) or, for GLOBAL, answered from the local replica and
reconciled asynchronously.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

from gubernator_tpu.api.types import (
    Behavior,
    HealthCheckResp,
    MAX_BATCH_SIZE,
    PeerInfo,
    RateLimitReq,
    RateLimitResp,
    Status,
    UpdatePeerGlobal,
    has_behavior,
)
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.parallel.global_sync import ORIGIN_MD_KEY
from gubernator_tpu.parallel.leases import (
    LEASE_REVOKE_MD_KEY,
    RETRY_AFTER_MD_KEY,
)
from gubernator_tpu.runtime.engine import DeviceEngine
from gubernator_tpu.service.admission import (
    DecisionRecorder,
    PATH_FORWARDED,
    PATH_OWNER,
    PATH_REPLICA,
    stamp_decision,
)
from gubernator_tpu.utils import clock as _clock
from gubernator_tpu.utils import tracing

# Bound on the replica-staleness map (key -> last owner-broadcast wall ms).
# LRU eviction: staleness metadata is best-effort observability, so the
# oldest-touched keys fall out first rather than growing without bound.
_STALENESS_MAP_MAX = 8192


class ApiError(Exception):
    """Whole-call failure, mapped to gRPC OUT_OF_RANGE / HTTP 400 etc."""

    def __init__(self, message: str, grpc_code: str = "INVALID_ARGUMENT", http_code: int = 400):
        super().__init__(message)
        self.grpc_code = grpc_code
        self.http_code = http_code


class V1Service:
    def __init__(
        self,
        engine: DeviceEngine,
        metrics: Optional[Metrics] = None,
        local_info: Optional[PeerInfo] = None,
        force_global: bool = False,
        now_fn=_clock.now_ms,
        admission_ring: int = 256,
    ):
        self.engine = engine
        self.metrics = metrics or Metrics()
        self.local_info = local_info or PeerInfo(is_owner=True)
        self.force_global = force_global
        self.now_fn = now_fn
        # Peer mesh seams, wired by the daemon (tasks: peers, global)
        self.picker = None  # PeerPicker; None => every key is local
        self.forwarder = None  # PeerForwarder for non-owner items
        self.global_mgr = None  # GlobalManager for GLOBAL behavior
        self.region_mgr = None  # RegionManager for MULTI_REGION behavior
        # Graceful-drain state (docs/robustness.md): flipped by
        # Daemon.close() before teardown starts. /readyz and HealthCheck
        # report it so orchestrators stop routing without killing the
        # pod early; the node keeps serving while it drains.
        self.draining = False
        self._peers_lock = asyncio.Lock()
        # Consistency observatory seams (docs/monitoring.md "Consistency"):
        # last owner-broadcast arrival per GLOBAL key (feeds the
        # global_staleness_ms response metadata under GUBER_STAGE_METADATA)
        # and the background divergence auditor, wired by the daemon.
        self._global_last_update: "OrderedDict[str, int]" = OrderedDict()
        self.auditor = None  # ConsistencyAuditor; None when not wired
        self.profiler = None  # ContinuousProfiler; None when not wired
        # Cooperative token leases (docs/architecture.md "Cooperative
        # leases"): the owner-side authority, wired by the daemon when
        # GUBER_LEASES is on. None (default) keeps every path bit-exact
        # with the pre-lease daemon.
        self.lease_mgr = None
        # Server-suggested backoff (GUBER_RETRY_AFTER): OVER_LIMIT
        # responses carry retry_after_ms derived from reset_time.
        self.retry_after = False
        # Replica-noted lease revocations (key -> owner-clock ms until
        # which grants are refused), learned from the LEASE_REVOKE_MD_KEY
        # riding owner broadcasts. Bounded LRU like the staleness map.
        self._lease_revoked: "OrderedDict[str, int]" = OrderedDict()
        # pre-resolved metric children (labels() lookups are hot-loop cost)
        m = self.metrics
        self._m_local = m.getratelimit_counter.labels("local")
        self._m_global = m.getratelimit_counter.labels("global")
        self._m_forward = m.getratelimit_counter.labels("forward")
        # Admission observatory (docs/monitoring.md "Admission"): every
        # answer this node produces is counted by serving path and logged
        # in the bounded flight recorder; the scrape-time bridge publishes
        # the node's measured over-admission ratio from the engine's
        # TTL-cached admission scan.
        self.recorder = DecisionRecorder(self.metrics, ring_size=admission_ring)
        self.metrics.add_sync(self._admission_sync)
        # SLO observatory + self-watchdog seams (docs/monitoring.md
        # "SLOs & burn rates"), wired by the daemon. The sync bridge is
        # registered unconditionally and no-ops until wired.
        self.slo = None  # SloObservatory
        self.watchdog = None  # Watchdog
        self.metrics.add_sync(self._slo_sync)
        # Overload control plane seam (service/overload.py), wired by
        # the daemon under GUBER_OVERLOAD. None (default) keeps intake
        # and forwarding bit-exact with the pre-overload daemon; the
        # sync bridge is registered unconditionally and no-ops unwired.
        self.overload = None  # OverloadManager
        self.metrics.add_sync(self._overload_sync)
        # Crash-tolerant ownership seam (parallel/standby.py), wired by
        # the daemon under GUBER_STANDBY. None (default) keeps every
        # path — including TransferSnapshots payload handling — bit-exact
        # with the pre-standby daemon.
        self.standby = None  # ReplicationManager

    # ---- V1.GetRateLimits (reference gubernator.go:183-309) ----------------

    async def get_rate_limits(
        self, reqs: Sequence[RateLimitReq], call=tracing.NO_CALL
    ) -> List[RateLimitResp]:
        """`call` (tracing.CallRecord, gRPC handlers only) takes this
        path's two stages: `route` is everything here but the awaits on
        engine futures, which are `engine_wait`."""
        m = self.metrics
        if len(reqs) > MAX_BATCH_SIZE:
            m.check_error_counter.labels("Request too large").inc()
            raise ApiError(
                f"Requests.RateLimits list too large; max size is '{MAX_BATCH_SIZE}'",
                grpc_code="OUT_OF_RANGE",
            )
        m.concurrent_checks.inc()
        t0 = time.perf_counter()
        try:
            # Request span: the engine links the flush span that serves
            # each batch back to this span (and vice versa) across the
            # batch boundary — see runtime/engine.py _start_flush_span
            # and docs/monitoring.md "Tracing the pipeline".
            with tracing.span(
                "V1Instance.GetRateLimits", level="INFO", items=len(reqs)
            ):
                return await self._get_rate_limits(reqs, call)
        finally:
            call.mark("route")
            m.concurrent_checks.dec()
            m.func_duration.labels("V1Instance.GetRateLimits").observe(
                time.perf_counter() - t0
            )

    async def _get_rate_limits(
        self, reqs: Sequence[RateLimitReq], call
    ) -> List[RateLimitResp]:
        m = self.metrics
        # In a capture, `route` as far as its first await: the routing
        # loop and the submissions to the engine.
        call.open("route")
        now = self.now_fn()
        n = len(reqs)
        responses: List[Optional[RateLimitResp]] = [None] * n
        local_items: List[tuple] = []  # (idx, req) -> bulk engine submit
        global_items: List[tuple] = []  # (idx, req, owner_info) -> bulk
        forward_tasks = []

        from gubernator_tpu.api.types import validate_request

        GLOBAL = int(Behavior.GLOBAL)  # plain-int flag tests in the hot loop
        for i, req in enumerate(reqs):
            err = validate_request(req)
            if err is not None:
                m.check_error_counter.labels("Invalid request").inc()
                responses[i] = RateLimitResp(error=err)
                continue
            if req.created_at is None or req.created_at == 0:
                req.created_at = now
            if self.force_global:
                req.behavior |= GLOBAL

            key = req.hash_key()
            try:
                peer = self._get_peer(key)
            except Exception as e:
                m.check_error_counter.labels("Error in GetPeer").inc()
                responses[i] = RateLimitResp(
                    error=f"Error in GetPeer, looking up peer that owns rate limit '{key}': {e}"
                )
                continue

            if peer.info.is_owner:
                self._m_local.inc()
                local_items.append((i, req))
            elif req.behavior & GLOBAL:
                self._m_global.inc()
                global_items.append((i, req, peer.info))
            else:
                self._m_forward.inc()
                forward_tasks.append(
                    (i, asyncio.ensure_future(self._forward(peer, req)))
                )

        # GLOBAL non-owner items: ONE bulk submission against the local
        # replica (reference answers each from the local cache,
        # gubernator.go:395-421 — per-item dispatch would force one engine
        # flush per item via NO_BATCHING). Both bulks are SUBMITTED before
        # either is awaited so the pump can coalesce them into one flush.
        global_fut = None
        if global_items:
            import dataclasses

            strip = not getattr(self.engine, "routes_global_internally", False)
            bulk_reqs = []
            for _, req, _owner in global_items:
                r2 = dataclasses.replace(req, metadata=dict(req.metadata))
                r2.behavior = req.behavior | Behavior.NO_BATCHING
                if strip:
                    r2.behavior &= ~Behavior.GLOBAL
                bulk_reqs.append(r2)
            global_fut = self.engine.check_bulk(bulk_reqs, call=call.seq)

        local_fut = None
        if local_items:
            local_fut = self.engine.check_bulk(
                [r for _, r in local_items], call=call.seq
            )

        stage_md = bool(getattr(self.engine.cfg, "stage_metadata", False))
        if global_fut is not None:
            try:
                call.mark("route")
                results = await asyncio.wrap_future(global_fut)
                call.mark("engine_wait")
                for (i, req, owner), resp in zip(global_items, results):
                    if self.global_mgr is not None:
                        self.global_mgr.queue_hit(req)
                    # Merge, don't replace: the engine may have attached
                    # stage_breakdown_us (GUBER_STAGE_METADATA) already.
                    resp.metadata["owner"] = owner.grpc_address
                    self._attach_retry_after(resp, now)
                    # Replica-staleness bound: age of the last owner
                    # broadcast applied locally for this key. Absent
                    # until the first broadcast lands (a fresh replica
                    # has no bound to honestly report).
                    ts = self._global_last_update.get(req.hash_key())
                    stale = max(0, now - ts) if ts is not None else None
                    if stage_md:
                        if stale is not None:
                            resp.metadata["global_staleness_ms"] = str(stale)
                        stamp_decision(resp, PATH_REPLICA, stale)
                    self.recorder.record_decision(
                        PATH_REPLICA,
                        resp,
                        key=req.hash_key(),
                        staleness_ms=stale or 0,
                    )
                    responses[i] = resp
            except Exception as e:
                for i, _, _ in global_items:
                    responses[i] = RateLimitResp(error=str(e))

        if local_fut is not None:
            try:
                call.mark("route")
                results = await asyncio.wrap_future(local_fut)
                call.mark("engine_wait")
                for (i, req), resp in zip(local_items, results):
                    responses[i] = resp
                    if resp.error:
                        self.recorder.record_decision(
                            PATH_OWNER, resp, key=req.hash_key()
                        )
                        continue
                    self._attach_retry_after(resp, now)
                    # Owner answers are authoritative: staleness bound 0.
                    if stage_md:
                        stamp_decision(resp, PATH_OWNER, 0)
                    self.recorder.record_decision(
                        PATH_OWNER, resp, key=req.hash_key()
                    )
                    # Replication legs queue only AFTER a successful local
                    # apply (reference gubernator.go:603-606 order) — a
                    # failed apply must not push hits it never counted.
                    if self.global_mgr is not None and (req.behavior & GLOBAL):
                        self.global_mgr.queue_update(req)
                    if self.region_mgr is not None and (
                        req.behavior & int(Behavior.MULTI_REGION)
                    ):
                        # In-region owner applied a MULTI_REGION item:
                        # queue the cross-region leg (delta toward the
                        # home region, or authoritative broadcast from it).
                        self.region_mgr.observe(req)
            except Exception as e:
                for i, _ in local_items:
                    responses[i] = RateLimitResp(error=str(e))

        if forward_tasks:
            call.mark("route")  # closes the capture's span before an await
        for i, task in forward_tasks:
            try:
                resp = await task
            except Exception as e:
                m.check_error_counter.labels("Error in asyncRequests").inc()
                resp = RateLimitResp(error=str(e))
            else:
                # The degraded-local fallback stamps its own provenance
                # (peers.py _owner_unreachable + its recorder hook) —
                # don't overwrite it or double-count here. The "degraded"
                # marker is unconditional there, unlike the stage_md-gated
                # path stamp, so it discriminates at every knob setting.
                degraded = bool(resp.metadata) and "degraded" in resp.metadata
                if not degraded:
                    if stage_md and not resp.error:
                        # Answered by the owner's engine: authoritative.
                        stamp_decision(resp, PATH_FORWARDED, 0)
                    self.recorder.record_decision(
                        PATH_FORWARDED, resp, key=reqs[i].hash_key()
                    )
            responses[i] = resp
        return [r if r is not None else RateLimitResp(error="internal: no response") for r in responses]

    def _get_peer(self, key: str):
        """Hash-ring lookup (reference gubernator.go:714-725); a standalone
        daemon (no peers configured) owns every key."""
        if self.picker is None or not self.picker.peers():
            return _LocalPeer(self.local_info)
        return self.picker.get(key)

    async def _forward(self, peer, req: RateLimitReq) -> RateLimitResp:
        if self.forwarder is None:
            raise RuntimeError("no peer forwarder configured")
        return await self.forwarder.forward(peer, req)

    def _attach_retry_after(self, resp: RateLimitResp, now: int) -> None:
        """Server-suggested backoff (GUBER_RETRY_AFTER, default off):
        OVER_LIMIT answers carry the ms until the window refills. Gated
        so the off state stays bit-exact with today's responses."""
        if (
            self.retry_after
            and resp.status == Status.OVER_LIMIT
            and not resp.error
        ):
            resp.metadata.setdefault(
                RETRY_AFTER_MD_KEY, str(max(0, resp.reset_time - now))
            )

    # ---- V1/PeersV1.Lease (cooperative token leases) -----------------------

    def _lease_reject(self, g: dict, error: str, retry_after_ms: int = 0) -> dict:
        return {
            "ok": 0, "lease_id": "", "slice": 0, "ttl_ms": 0,
            "expiry_ms": 0, "limit": int(g.get("limit", 0)), "remaining": 0,
            "reset_time": 0, "retry_after_ms": retry_after_ms, "error": error,
        }

    async def lease(
        self,
        grants: List[dict],
        returns: List[dict],
        holder: str = "",
        no_forward: bool = False,
    ) -> tuple:
        """Route one Lease RPC: rows for keys this daemon owns go to the
        local LeaseManager; the rest forward to their owners over
        PeersV1/Lease (one hop — `no_forward` stops ring-view
        disagreements from looping). Returns (grant_results,
        return_results), positional with the inputs."""
        now = self.now_fn()
        g_res: List[Optional[dict]] = [None] * len(grants)
        r_res: List[Optional[dict]] = [
            {"lease_id": str(r.get("lease_id", "")), "status": "unknown"}
            for r in returns
        ]
        local_g: List[int] = []
        local_r: List[int] = []
        remote: Dict[str, tuple] = {}  # addr -> (peer, g_idx, r_idx)

        def _route(key: str):
            try:
                return self._get_peer(key), None
            except Exception as e:  # guberlint: allow-swallow -- ring empty / picker failure becomes a per-row UNAVAILABLE reject, not a dropped error
                return None, str(e)

        for i, g in enumerate(grants):
            key = str(g.get("name", "")) + "_" + str(g.get("unique_key", ""))
            until = self._lease_revoked.get(key)
            if until is not None and until > now:
                g_res[i] = self._lease_reject(g, "revoked", until - now)
                continue
            peer, err = _route(key)
            if peer is None:
                g_res[i] = self._lease_reject(g, f"UNAVAILABLE: {err}")
            elif peer.info.is_owner:
                local_g.append(i)
            elif no_forward:
                g_res[i] = self._lease_reject(g, "UNAVAILABLE: not owner")
            else:
                addr = peer.info.grpc_address
                ent = remote.setdefault(addr, (peer, [], []))
                ent[1].append(i)
        for i, r in enumerate(returns):
            key = str(r.get("name", "")) + "_" + str(r.get("unique_key", ""))
            peer, err = _route(key)
            if peer is None:
                continue  # stays "unknown"; the holder drops its copy
            if peer.info.is_owner:
                local_r.append(i)
            elif not no_forward:
                addr = peer.info.grpc_address
                ent = remote.setdefault(addr, (peer, [], []))
                ent[2].append(i)

        if local_g or local_r:
            if self.lease_mgr is None:
                for i in local_g:
                    g_res[i] = self._lease_reject(grants[i], "leases disabled")
            else:
                gr, rr = await self.lease_mgr.handle(
                    [grants[i] for i in local_g],
                    [returns[i] for i in local_r],
                    holder=holder,
                )
                for i, res in zip(local_g, gr):
                    g_res[i] = res
                for i, res in zip(local_r, rr):
                    r_res[i] = res

        if remote:
            from gubernator_tpu.service import pb as _pb

            async def _one(peer, g_idx, r_idx):
                md = tracing.propagate_inject({"no_forward": "1"})
                payload = _pb.lease_req_to_bytes(
                    [grants[i] for i in g_idx],
                    [returns[i] for i in r_idx],
                    holder=holder, metadata=md,
                )
                raw = await peer.lease(payload)
                return _pb.lease_resp_from_bytes(raw)

            ents = list(remote.values())
            outs = await asyncio.gather(
                *(_one(p, gi, ri) for p, gi, ri in ents),
                return_exceptions=True,
            )
            for (peer, g_idx, r_idx), out in zip(ents, outs):
                if isinstance(out, BaseException):
                    for i in g_idx:
                        g_res[i] = self._lease_reject(
                            grants[i], f"UNAVAILABLE: {out}"
                        )
                    continue  # returns stay "unknown"
                gr, rr, _md = out
                for i, res in zip(g_idx, gr):
                    g_res[i] = res
                for i, res in zip(r_idx, rr):
                    r_res[i] = res

        for i, g in enumerate(grants):
            if g_res[i] is None:
                g_res[i] = self._lease_reject(g, "internal: no response")
        return g_res, r_res

    def _note_lease_revoked(self, key: str, until_ms: int) -> None:
        """Record a revocation learned from an owner broadcast (LRU,
        bounded like the staleness map; event-loop only)."""
        mp = self._lease_revoked
        mp[key] = max(mp.get(key, 0), until_ms)
        mp.move_to_end(key)
        while len(mp) > _STALENESS_MAP_MAX:
            mp.popitem(last=False)

    # ---- PeersV1.GetPeerRateLimits (reference gubernator.go:462-539) -------

    async def get_peer_rate_limits(
        self, reqs: Sequence[RateLimitReq], call=tracing.NO_CALL
    ) -> List[RateLimitResp]:
        """`call`: as in get_rate_limits."""
        try:
            return await self._get_peer_rate_limits(reqs, call)
        finally:
            call.mark("route")

    async def _get_peer_rate_limits(
        self, reqs: Sequence[RateLimitReq], call
    ) -> List[RateLimitResp]:
        if len(reqs) > MAX_BATCH_SIZE:
            self.metrics.check_error_counter.labels("Request too large").inc()
            raise ApiError(
                f"'PeerRequest.rate_limits' list too large; max size is '{MAX_BATCH_SIZE}'",
                grpc_code="OUT_OF_RANGE",
            )
        from gubernator_tpu.utils import tracing

        call.open("route")  # in a capture: as far as the first await
        has_global = False
        for req in reqs:
            # Extract the forwarding peer's trace context from the item's
            # metadata (reference gubernator.go:503-504).
            ctx = tracing.propagate_extract(req.metadata)
            if ctx is not None:
                with tracing.attached(ctx):
                    # Per-peer span: DEBUG-level, dropped at the default
                    # INFO trace level (reference config.go:736-752).
                    with tracing.span(
                        "V1Instance.getLocalRateLimit",
                        level="DEBUG",
                        key=req.hash_key(),
                    ):
                        pass
            if has_behavior(req.behavior, Behavior.GLOBAL):
                # Owner handling a relayed GLOBAL hit always drains
                # (reference gubernator.go:510-512) and queues a broadcast.
                req.behavior |= Behavior.DRAIN_OVER_LIMIT
                has_global = True
            if req.created_at is None or req.created_at == 0:
                req.created_at = self.now_fn()
        t_apply = time.perf_counter()
        try:
            call.mark("route")
            results = await asyncio.wrap_future(
                self.engine.check_bulk(list(reqs), call=call.seq)
            )
            call.mark("engine_wait")
        except Exception as e:
            return [RateLimitResp(error=str(e)) for _ in reqs]
        if has_global:
            # owner_apply leg: relayed-hit batch arrival to engine apply
            # done — the owner's contribution to propagation lag.
            self.metrics.global_sync_leg_duration.labels("owner_apply").observe(
                time.perf_counter() - t_apply
            )
        now = self.now_fn()
        for req, resp in zip(reqs, results):
            if resp.error:
                continue
            self._attach_retry_after(resp, now)
            # Replication legs queue only AFTER a successful apply — a
            # failed apply must not push hits it never counted.
            if self.global_mgr is not None and has_behavior(req.behavior, Behavior.GLOBAL):
                self.global_mgr.queue_update(req)
            if self.region_mgr is not None and has_behavior(
                req.behavior, Behavior.MULTI_REGION
            ):
                # Both in-region forwards and cross-region deltas land
                # here; the same rule covers both — the applying node is
                # the in-region owner, so it queues the cross-region leg.
                self.region_mgr.observe(req)
        return results

    # ---- PeersV1.UpdatePeerGlobals (reference gubernator.go:425-459) -------

    async def update_peer_globals(self, globals_: Sequence[UpdatePeerGlobal]) -> None:
        m = self.metrics
        now_ms = self.now_fn()
        trace_id = tracing.trace_id_of(tracing.current_span())
        for g in globals_:
            md = getattr(g.status, "metadata", None)
            revoke = md.pop(LEASE_REVOKE_MD_KEY, None) if md else None
            if revoke is not None:
                # Revocation riding the broadcast leg: refuse new grants
                # for this key here too, so a holder renewing through a
                # replica is turned away without an extra owner hop.
                try:
                    self._note_lease_revoked(g.key, int(revoke))
                except ValueError:
                    pass
            origin = md.pop(ORIGIN_MD_KEY, None) if md else None
            if origin is not None:
                # Close the end-to-end loop: origin stamp (sampled at the
                # hit's first enqueue) to this replica applying the owner's
                # broadcast. Cross-node wall clocks — read alongside
                # gubernator_peer_clock_skew_ms; clamp at 0 so a skewed
                # clock can't underflow the histogram.
                try:
                    lag_s = max(0.0, (now_ms - int(origin)) / 1000.0)
                except ValueError:
                    pass
                else:
                    m.global_propagation_lag.observe(lag_s, trace_id=trace_id)
            self._note_global_update(g.key, now_ms)
        loop = asyncio.get_running_loop()
        t0 = time.perf_counter()
        await loop.run_in_executor(None, self.engine.inject_globals, globals_)
        m.global_sync_leg_duration.labels("replica_inject").observe(
            time.perf_counter() - t0
        )

    def _note_global_update(self, key: str, now_ms: int) -> None:
        """Record an owner-broadcast arrival for the staleness map (LRU,
        bounded at _STALENESS_MAP_MAX; event-loop only, no lock needed)."""
        mp = self._global_last_update
        mp[key] = now_ms
        mp.move_to_end(key)
        while len(mp) > _STALENESS_MAP_MAX:
            mp.popitem(last=False)

    # ---- PeersV1.TransferSnapshots (ownership handover) --------------------

    async def transfer_snapshots(self, snaps, leases=None) -> tuple:
        """Receiver half of ring-change/drain handover: merge incoming
        counter state last-writer-wins on stamp (docs/robustness.md
        "Rolling restarts & handover"). `leases` carries the sender's
        outstanding lease records for the re-homed keys (same LWW
        discipline, keyed on lease id) so holders keep serving through
        the handover without re-granting. Returns (accepted, stale)."""
        from gubernator_tpu.store.store import merge_snapshots_lww

        loop = asyncio.get_running_loop()
        accepted, stale = await loop.run_in_executor(
            None, merge_snapshots_lww, self.engine, list(snaps)
        )
        m = self.metrics
        if accepted:
            m.handover_keys_received.inc(accepted)
        if stale:
            m.handover_keys_dropped.labels("stale").inc(stale)
        if leases and self.lease_mgr is not None:
            self.lease_mgr.adopt(leases)
        return accepted, stale

    # ---- V1.HealthCheck (reference gubernator.go:542-586) ------------------

    async def health_check(self) -> HealthCheckResp:
        errors: List[str] = []
        peer_count = 0
        open_circuits: List[str] = []
        if self.picker is not None:
            peer_count = len(self.picker.peers())
            if hasattr(self.picker, "region_peers"):
                peer_count += len(self.picker.region_peers())
            if self.forwarder is not None:
                errors = self.forwarder.recent_errors()
                if hasattr(self.forwarder, "breaker_summary"):
                    open_circuits = sorted(
                        a
                        for a, s in self.forwarder.breaker_summary().items()
                        if s != "closed"
                    )
        if self.draining:
            # Drain state outranks the error log: the node is leaving on
            # purpose; orchestrators should stop routing, not restart it
            # (cmd/healthcheck.py exits 2 on this status).
            return HealthCheckResp(
                status="draining",
                message="graceful drain in progress; stop routing",
                peer_count=peer_count,
            )
        if errors:
            msg = "; ".join(errors[:3])
            if open_circuits:
                # Breaker summary rides the reference-shaped message so
                # probes see WHICH fault domain is dark, not just that
                # errors happened in the last 5 minutes.
                msg = f"circuits open: {', '.join(open_circuits)}; {msg}"
            return HealthCheckResp(
                status="unhealthy", message=msg, peer_count=peer_count
            )
        return HealthCheckResp(status="healthy", peer_count=peer_count)

    def readiness(self) -> dict:
        """Readiness for the /readyz probe (docs/robustness.md): unlike
        the TTL'd error log feeding health_check — where one flapping
        peer marks the node unhealthy for a full 5 minutes — readiness
        derives from CURRENT breaker state, so it flips back the moment
        a dead peer's circuit closes.

        ready    — every peer circuit closed (or no mesh at all)
        degraded — some circuits open; keys owned by surviving peers
                   still serve within SLO
        unready  — every remote peer's circuit is open (the node cannot
                   reach any fault domain but its own)
        draining — graceful shutdown in progress: stop routing here, but
                   do NOT kill the pod — queued work is finishing and
                   owned keys are handing off to ring successors
        """
        summary = {}
        if self.forwarder is not None and hasattr(self.forwarder, "breaker_summary"):
            summary = self.forwarder.breaker_summary()
        open_circuits = sorted(a for a, s in summary.items() if s == "open")
        if self.draining:
            status = "draining"
        elif summary and len(open_circuits) == len(summary):
            status = "unready"
        elif open_circuits:
            status = "degraded"
        else:
            status = "ready"
        return {
            "status": status,
            "peers": len(summary),
            "open_circuits": open_circuits,
        }

    # ---- consistency observatory (docs/monitoring.md "Consistency") --------

    def local_debug_info(self, keys: Optional[Sequence[str]] = None) -> dict:
        """One node's slice of the cluster debug view: health, breaker
        states, occupancy, hot keys, and consistency gauges in a single
        JSON-able blob. Served locally under /debug/cluster (gateway) and
        remotely over PeersV1.DebugInfo — always LOCAL state only, so the
        fan-out cannot recurse. With `keys`, also returns those keys'
        counter snapshots (the divergence auditor's replica-view fetch).
        Runs engine readbacks; call from an executor on hot paths."""
        m = self.metrics
        info: dict = {
            "v": 1,
            "now_ms": self.now_fn(),
            "address": self.local_info.grpc_address,
            "readiness": self.readiness(),
        }
        if self.forwarder is not None and hasattr(self.forwarder, "breaker_summary"):
            info["breakers"] = self.forwarder.breaker_summary()
        if hasattr(self.engine, "occupancy_stats"):
            info["occupancy"] = self.engine.occupancy_stats()
        if hasattr(self.engine, "table_census"):
            # Full census rides the free-form DebugInfo dict, so
            # /debug/cluster aggregates a fleet-wide table observatory
            # with no wire-format bump (docs/monitoring.md).
            info["table_census"] = self.engine.table_census()
        if hasattr(self.engine, "hotkeys_snapshot"):
            info["hotkeys"] = self.engine.hotkeys_snapshot()
        # Device-resource blob rides the free-form DebugInfo dict too,
        # so /debug/cluster shows fleet-wide HBM headroom and transfer
        # bandwidth with no wire-format bump (docs/monitoring.md
        # "Device resources").
        info["device"] = self.device_debug_info()
        # Admission blob rides DebugInfo as well (sans flight-recorder
        # ring — 256 rows per node is wire weight the fleet view doesn't
        # need; /debug/admission serves the ring locally): the auditor's
        # admission pass reads each node's measured window accounting and
        # over-admission bound from here.
        info["admission"] = self.admission_debug_info(include_ring=False)
        consistency: dict = {
            "propagation_lag": m.global_propagation_lag.summary(),
            "staleness_keys_tracked": len(self._global_last_update),
        }
        if self.auditor is not None:
            consistency.update(self.auditor.summary())
        info["consistency"] = consistency
        if self.lease_mgr is not None:
            # Lease ledger rides the free-form DebugInfo dict like the
            # census — /debug/cluster aggregates fleet-wide outstanding
            # slices (the over-admission bound) with no wire bump.
            info["leases"] = self.lease_mgr.summary()
        if self.slo is not None:
            # Compact SLO blob (per-SLO alert state + remaining error
            # budget, no ring dumps) rides DebugInfo so /debug/cluster
            # shows the fleet-wide budget view (docs/monitoring.md
            # "SLOs & burn rates").
            info["slo"] = self.slo.fleet_info()
        if self.standby is not None:
            # Standby summary (loss bound, shadow inventory, promotions)
            # rides DebugInfo like the census, so /debug/cluster shows
            # the fleet-wide durability picture with no wire bump.
            info["standby"] = self.standby.summary()
        if self.overload is not None:
            # Brownout ladder blob (level, signals, intake governor
            # state) rides DebugInfo so /debug/cluster shows which
            # nodes are degraded and why (docs/robustness.md "Overload
            # control & brownout").
            info["overload"] = self.overload.debug_info()
        if keys:
            from gubernator_tpu.store.store import snapshots_from_engine

            wanted = set(keys)
            info["snapshots"] = [
                dataclasses.asdict(s)
                for s in snapshots_from_engine(self.engine)
                if s.key in wanted
            ]
            # Per-key broadcast-arrival stamps: the transport-level
            # replica view the auditor compares against the owner's
            # broadcast ledger (algorithm-agnostic, unlike raw counter
            # state — leaky injects re-stamp updated_at on arrival).
            info["global_updates"] = {
                k: self._global_last_update[k]
                for k in keys
                if k in self._global_last_update
            }
        return info

    def admission_debug_info(self, include_ring: bool = True) -> dict:
        """/debug/admission payload (docs/monitoring.md "Admission"):
        the engine's TTL-cached ground-truth window accounting, the
        decision counters by path, the over-admission BOUND this node
        contributes (outstanding lease hits + queued GLOBAL hits not yet
        relayed), and — locally only — the decision flight recorder.
        Scrape-safe: the engine snapshot is TTL-cached (GL009), the rest
        is host-side dict copies."""
        blob: dict = {"v": 1}
        if hasattr(self.engine, "admission_snapshot"):
            blob["window"] = self.engine.admission_snapshot()
        rec = self.recorder.snapshot()
        blob["decisions"] = rec["decisions"]
        blob["ring_size"] = rec["ring_size"]
        if include_ring:
            blob["ring"] = rec["ring"]
        # The over-admission bound: hits this node has admitted (or will
        # admit) that the owners' tables have not yet absorbed. During a
        # partition the fleet's measured excess must stay within the sum
        # of these across nodes; after heal both legs drain to 0.
        bound: dict = {}
        if self.lease_mgr is not None:
            bound["lease_outstanding_hits"] = int(
                self.lease_mgr.outstanding_hits()
            )
        if self.global_mgr is not None and hasattr(
            self.global_mgr, "inflight_hits"
        ):
            bound["global_inflight_hits"] = int(
                self.global_mgr.inflight_hits()
            )
        bound["total_hits"] = sum(bound.values())
        blob["bound"] = bound
        return blob

    def standby_debug_info(self) -> dict:
        """/debug/standby payload (docs/robustness.md "Standby
        replication & crash recovery"): the published loss bound, the
        pending (unacked) ledger, shadow inventory by source owner, and
        promotion history. Host-side dict copies only — the loss bound
        reads the engine's dirty registry under its own lock, never the
        device (GL009)."""
        if self.standby is None:
            return {"enabled": False}
        return self.standby.summary()

    def slo_debug_info(self) -> dict:
        """/debug/slo payload (docs/monitoring.md "SLOs & burn rates"):
        per-SLO burn rates over every evaluation window, alert states,
        remaining error budgets, the sampled SLI ring summaries, and
        the watchdog's per-loop heartbeat table. Pure ring arithmetic
        over already-sampled values — zero device work (GL009)."""
        if self.slo is None:
            return {"enabled": False}
        return {"enabled": True, **self.slo.debug_info()}

    def overload_debug_info(self) -> dict:
        """/debug/overload payload (docs/robustness.md "Overload
        control & brownout"): the brownout ladder level + driving
        signals and the intake governor's controller state (shed
        counts by reason, tenant weights, heavy-hitter attribution).
        Host-side dict copies only — zero device work (GL009)."""
        if self.overload is None:
            return {"enabled": False}
        return self.overload.debug_info()

    def _overload_sync(self, _metrics=None) -> None:
        """Scrape-time bridge for gubernator_overload_level. No-op
        until the daemon wires the overload manager."""
        if self.overload is None:
            return
        try:
            self.overload.metrics_sync(self.metrics)
        except Exception:  # guberlint: allow-swallow -- scrape bridge: a failed ladder read must not poison /metrics
            return

    def _slo_sync(self, _metrics=None) -> None:
        """Scrape-time bridge for the SLO families (burn rate, budget
        remaining, alert state) and gubernator_thread_stalled. No-op
        until the daemon wires the observatory."""
        if self.slo is None:
            return
        try:
            self.slo.metrics_sync(self.metrics)
        except Exception:  # guberlint: allow-swallow -- scrape bridge: a failed evaluation must not poison /metrics
            return

    def _admission_sync(self, _metrics=None) -> None:
        """Scrape-time bridge: publish this node's measured over-admission
        ratio (excess hits / configured limit over active windows, from
        the engine's TTL-cached admission scan). Single writer for
        gubernator_admission_excess_ratio — the auditor's fleet-max lives
        in a separate gauge (admission_audit_max_excess_ratio).
        Metrics.sync() passes the Metrics instance to every callback;
        this bound method already closes over self.metrics."""
        if not hasattr(self.engine, "admission_snapshot"):
            return
        try:
            snap = self.engine.admission_snapshot()
        except Exception:  # guberlint: allow-swallow -- scrape bridge: a failed scan must not poison /metrics
            return
        self.metrics.admission_excess_ratio.set(
            float(snap.get("excess_ratio", 0.0))
        )

    def device_debug_info(self) -> dict:
        """/debug/device payload (docs/monitoring.md "Device
        resources"): the platform, device kind and device count JAX
        initialised, per-subsystem HBM attribution + headroom with one
        row per device the engine spans, the host<->device transfer
        ledger, compile telemetry with retrace attribution, and
        profiler capture stats. Host-side reads only — allocator stats,
        histogram summaries, bounded ring copies — so scraping it never
        dispatches device work (GL009)."""
        from gubernator_tpu.runtime import telemetry as _rt
        from gubernator_tpu.utils import compilecache, devicemem

        info: dict = {"v": 1, **devicemem.process_devices()}
        if hasattr(self.engine, "device_memory"):
            info["memory"] = self.engine.device_memory()
        em = getattr(self.engine, "metrics", None)
        if em is not None and hasattr(em, "transfer_snapshot"):
            info["transfers"] = em.transfer_snapshot()
        info["compile"] = compilecache.cache_stats()
        info["retraces"] = _rt.compile_attribution()
        prof = getattr(self, "profiler", None)
        if prof is not None and hasattr(prof, "stats"):
            info["profiler"] = prof.stats()
        return info

    # ---- peer membership (reference gubernator.go:616-711) -----------------

    def set_peers(self, peers: Sequence[PeerInfo]) -> None:
        """Swap in a new peer set; wired fully by the daemon/peers layer."""
        if self.picker is not None:
            self.picker.set_peers(peers, self.local_info)


class _LocalPeer:
    """Self-peer shim for daemons running without a mesh."""

    def __init__(self, info: PeerInfo):
        self.info = PeerInfo(
            grpc_address=info.grpc_address,
            http_address=info.http_address,
            data_center=info.data_center,
            is_owner=True,
        )
