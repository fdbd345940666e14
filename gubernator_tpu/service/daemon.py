"""Daemon: composition root (reference daemon.go:73-366).

Builds the device engine, core service, gRPC server (V1 + PeersV1), and
the HTTP gateway; exposes SetPeers for discovery backends and a client
helper for tests. One process can host many daemons (each with its own
engine/table/registry) — the in-process cluster fixture depends on that,
like the reference's cluster harness (cluster/cluster.go:151-189).
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import List, Optional, Sequence

import grpc
from aiohttp import web

from gubernator_tpu.api.types import PeerInfo
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.runtime.engine import DeviceEngine
from gubernator_tpu.service import rpc
from gubernator_tpu.service.config import DaemonConfig
from gubernator_tpu.service.gateway import build_app
from gubernator_tpu.service.grpc_service import PeersV1Servicer, V1Servicer
from gubernator_tpu.service.server import V1Service
from gubernator_tpu.utils import net, tracing

log = logging.getLogger("gubernator.daemon")


def _warn_missing_native() -> None:
    """One WARNING when a native library did not build or load: the
    process then hashes with Python xxh3 and/or serves every call
    through the protobuf object path — it keeps working, slower, and
    nothing else would say so."""
    from gubernator_tpu import native, wire

    for name, mod, effect in (
        ("guberhash", native, "key hashing falls back to Python xxh3"),
        ("wirepath", wire, "the columnar wire path is off"),
    ):
        if not mod.available():
            log.warning(
                "native library %s unavailable (%s): %s",
                name, mod.unavailable_reason, effect,
            )


class Daemon:
    def __init__(self, conf: DaemonConfig):
        self.conf = conf
        self.engine: Optional[DeviceEngine] = None
        self.svc: Optional[V1Service] = None
        self.grpc_server: Optional[grpc.aio.Server] = None
        self.http_runner: Optional[web.AppRunner] = None
        self.grpc_address = ""
        self.http_address = ""
        self.status_runner = None
        self.status_address = ""
        self._channel: Optional[grpc.aio.Channel] = None
        # Lifecycle: serving -> draining -> stopped (docs/robustness.md)
        self.state = "serving"

    # -- lifecycle -----------------------------------------------------------

    @classmethod
    async def spawn(cls, conf: DaemonConfig) -> "Daemon":
        d = cls(conf)
        await d.start()
        return d

    async def start(self) -> None:
        # NOTE: trace level is process-global (like the env var that sets
        # it); the CLI entry point applies conf.trace_level. A library
        # Daemon must not clobber other in-process daemons' tracing.
        conf = self.conf
        # Chaos-testing fault rules (GUBER_FAULTS); no-op when unset.
        from gubernator_tpu.utils import faults

        faults.configure_from_env()
        _warn_missing_native()
        if conf.global_mode == "ici":
            from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig

            self.engine = IciEngine(conf.ici or IciEngineConfig())
        else:
            self.engine = DeviceEngine(conf.engine_config())

        from gubernator_tpu.utils import devicemem

        # JAX falls back to CPU by itself; say once what this daemon got.
        pd = devicemem.process_devices()
        log.info(
            "device: platform=%s device_kind=%s device_count=%d "
            "engine_devices=%s",
            pd["platform"], pd["device_kind"], pd["device_count"],
            [str(d) for d in self.engine.devices],
        )

        # Persistence plugins (reference gubernator.go:138-148)
        if conf.store is not None:
            from gubernator_tpu.store import attach_store

            attach_store(self.engine, conf.store)
        if conf.loader is not None:
            from gubernator_tpu.store import load_engine

            load_engine(self.engine, conf.loader)

        # Optionally block startup until the kernel bucket ladder is
        # warm, so the very first NO_BATCHING request already gets a
        # width-sized kernel (GUBER_PREWARM_BUCKETS; cheap on restart
        # under the persistent compile cache — see utils/compilecache).
        if conf.prewarm_buckets and hasattr(self.engine, "wait_warm"):
            t0 = time.monotonic()
            done = await asyncio.get_running_loop().run_in_executor(
                None, self.engine.wait_warm, conf.prewarm_timeout_s
            )
            log.info(
                "bucket prewarm %s in %.1fs",
                "complete" if done else "TIMED OUT (serving anyway)",
                time.monotonic() - t0,
            )

        metrics = Metrics()
        from gubernator_tpu.metrics import wire_engine_telemetry

        # Scalar bridge + device-tier histogram exposition (flush
        # latency/width/waves, queue wait, ICI tick series, occupancy
        # gauges — docs/monitoring.md).
        wire_engine_telemetry(metrics, self.engine)

        # Optional OS/runtime collectors (reference daemon.go:276-287)
        flags = getattr(conf, "metric_flags", [])
        if "os" in flags:
            from prometheus_client import ProcessCollector

            ProcessCollector(registry=metrics.registry)
        if "golang" in flags:  # runtime collectors; Python GC here
            from prometheus_client import GCCollector, PlatformCollector

            PlatformCollector(registry=metrics.registry)
            GCCollector(registry=metrics.registry)

        self.svc = V1Service(
            self.engine,
            metrics=metrics,
            force_global=conf.behaviors.force_global,
            # knob: GUBER_ADMISSION_RING (decision flight recorder)
            admission_ring=getattr(conf, "admission_ring", 256),
        )
        # Server-suggested backoff (GUBER_RETRY_AFTER): OVER_LIMIT
        # responses carry retry_after_ms; off keeps responses bit-exact.
        self.svc.retry_after = conf.behaviors.retry_after
        # Columnar serving edge. A Store no longer disables it:
        # check_columns runs the same per-wave probe -> read-through ->
        # decide -> write-behind sequence as the object path (and records
        # key strings). A Loader-only daemon keeps the object path so the
        # key-string dictionary stays complete for snapshots without the
        # columnar path paying O(n) string decodes. GLOBAL (including
        # force_global) is served columnar too (fastpath.try_serve ORs
        # the flag in and queues the replication legs).
        self.svc.fast_edge = conf.loader is None or conf.store is not None

        # gRPC server hosting both services (reference daemon.go:139-167)
        # with the reference's hardening: 1MB receive cap (daemon.go:122)
        # and optional max-connection-age rotation (daemon.go:128-133).
        opts = [("grpc.max_receive_message_length", 1024 * 1024)]
        if conf.grpc_max_conn_age_s > 0:
            age_ms = int(conf.grpc_max_conn_age_s * 1000)
            opts += [
                ("grpc.max_connection_age_ms", age_ms),
                ("grpc.max_connection_age_grace_ms", age_ms),
            ]
        self.grpc_server = grpc.aio.server(options=opts)
        self.grpc_server.add_generic_rpc_handlers(
            (rpc.v1_handler(V1Servicer(self.svc)), rpc.peers_handler(PeersV1Servicer(self.svc)))
        )
        host = conf.grpc_listen_address.rsplit(":", 1)[0]
        if conf.tls is not None:
            from gubernator_tpu.service.tls import server_credentials, setup_tls

            setup_tls(conf.tls, hosts=[host if host not in ("0.0.0.0", "::") else "localhost", "127.0.0.1"])
            port = self.grpc_server.add_secure_port(
                conf.grpc_listen_address, server_credentials(conf.tls)
            )
        else:
            port = self.grpc_server.add_insecure_port(conf.grpc_listen_address)
        self.grpc_address = f"{host}:{port}"
        await self.grpc_server.start()
        # The serving loop's lateness and the interpreter lock's wait,
        # probed from here to the drain (docs/monitoring.md "Tracing
        # the pipeline").
        self._host_probes = tracing.HostProbes(
            metrics.loop_lag, metrics.interpreter_wait
        )
        self._host_probes.start(asyncio.get_running_loop())

        # Local identity must be known before peers are set
        advertise = conf.advertise_address or self.grpc_address

        # HTTP gateway + metrics (reference daemon.go:251-299); serves TLS
        # with the same certs as the gRPC listener when configured.
        self.http_runner = None
        self.http_address = ""
        if conf.http_listen_address:
            app = build_app(self.svc)
            self.http_runner = web.AppRunner(app)
            await self.http_runner.setup()
            # ":80" binds all interfaces (every family) Go-style; ""
            # disables the listener entirely (GUBER_HTTP_ADDRESS= in the
            # environment previously crashed spawn with an unpack error).
            hhost, hport = net.parse_listen_address(conf.http_listen_address)
            ssl_ctx = None
            if conf.tls is not None:
                from gubernator_tpu.service.tls import http_ssl_context

                ssl_ctx = http_ssl_context(conf.tls)
            site = web.TCPSite(
                self.http_runner, hhost, int(hport), ssl_context=ssl_ctx
            )
            await site.start()
            actual = site._server.sockets[0].getsockname()
            # Recorded address must be dialable: wildcard/all-interfaces
            # binds expand to a concrete interface IP (ADVICE r5).
            self.http_address = net.recorded_address(hhost, actual[1])

        # Optional health-only listener that never requests a client cert
        # (reference daemon.go:305-333): lets load balancers probe
        # /v1/HealthCheck on an mTLS deployment without presenting certs.
        self.status_runner = None
        self.status_address = ""
        if conf.status_http_listen_address:
            from gubernator_tpu.service.gateway import build_status_app

            status_app = build_status_app(self.svc)
            self.status_runner = web.AppRunner(status_app)
            await self.status_runner.setup()
            shost, sport = net.parse_listen_address(
                conf.status_http_listen_address
            )
            status_ssl = None
            if conf.tls is not None:
                from gubernator_tpu.service.tls import http_ssl_context

                status_ssl = http_ssl_context(conf.tls, no_client_auth=True)
            ssite = web.TCPSite(
                self.status_runner, shost, sport, ssl_context=status_ssl
            )
            await ssite.start()
            sactual = ssite._server.sockets[0].getsockname()
            self.status_address = net.recorded_address(shost, sactual[1])

        # Edge-tier listener: gubernator-tpu-edge processes relay client
        # calls here over framed RPC (service/edge.py) — same serving
        # core as the gRPC listener, minus the gRPC server cost.
        self.edge_listener = None
        if conf.edge_listen_address:
            from gubernator_tpu.service.edge import EdgeListener

            self.edge_listener = EdgeListener(self.svc, conf.edge_listen_address)
            await self.edge_listener.start()

        self.svc.local_info = PeerInfo(
            grpc_address=advertise,
            http_address=self.http_address,
            data_center=conf.data_center,
            is_owner=True,
        )

        # Peer mesh (hash ring + forwarder + global manager) is attached by
        # wire_peers(); a daemon with no peers serves everything locally.
        from gubernator_tpu.parallel.peers import wire_peers

        wire_peers(self, global_mode=conf.global_mode)

        # Cooperative token leases (docs/architecture.md "Cooperative
        # leases"): owner-side authority + expiry sweep, only under
        # GUBER_LEASES — the None default keeps every path bit-exact.
        self._lease_mgr = None
        if conf.behaviors.leases:
            from gubernator_tpu.parallel.leases import LeaseManager

            self._lease_mgr = LeaseManager(
                self.svc,
                ttl_s=conf.behaviors.lease_ttl_s,
                fraction=conf.behaviors.lease_fraction,
                max_leases=conf.behaviors.lease_max_keys,
                sweep_interval_s=conf.behaviors.lease_sweep_interval_s,
            )
            self.svc.lease_mgr = self._lease_mgr
            self._lease_mgr.start()

        # Crash-tolerant ownership (docs/robustness.md "Standby
        # replication & crash recovery"): every owner shadows its
        # counter state to its ring successors; standbys promote on
        # owner death. Only under GUBER_STANDBY — the None default (and
        # the engine's None dirty registry) keeps every path bit-exact
        # with the pre-standby daemon.
        self._standby = None
        if conf.behaviors.standby:
            from gubernator_tpu.parallel.standby import ReplicationManager

            self.engine.enable_dirty_tracking()
            self._standby = ReplicationManager(
                self.svc,
                conf.behaviors,
                local_addr=advertise,
                mesh=self.svc.picker,
            )
            self.svc.standby = self._standby
            self.svc.picker.standby = self._standby
            self._standby.start()

        # Background divergence auditor (consistency observatory,
        # docs/monitoring.md "Consistency"): samples broadcast keys and
        # verifies one replica's view per pass. Off when the audit
        # interval is 0 or the daemon has no GLOBAL manager to audit.
        self._auditor = None
        if self.svc.global_mgr is not None:
            from gubernator_tpu.parallel.auditor import ConsistencyAuditor

            self._auditor = ConsistencyAuditor(self.svc, conf.behaviors)
            self.svc.auditor = self._auditor
            self._auditor.start()

        # Continuous profiler (docs/monitoring.md "Device resources"):
        # off unless GUBER_PROFILE_INTERVAL > 0. Shares the one-capture-
        # at-a-time guard with /debug/profile; trace dirs rotate, so an
        # unattended soak holds profile_keep traces, not thousands.
        self._profiler = None
        if float(getattr(conf, "profile_interval_s", 0.0)) > 0:
            from gubernator_tpu.service.profiler import ContinuousProfiler

            self._profiler = ContinuousProfiler(
                conf.profile_interval_s,
                seconds=conf.profile_seconds,
                keep=conf.profile_keep,
            )
            self.svc.profiler = self._profiler
            self._profiler.start()

        # Self-watchdog + SLO observatory (docs/monitoring.md "SLOs &
        # burn rates"): every long-lived loop (engine pump, completion
        # thread, ICI sync, auditor, demoter, lease sweep, profiler,
        # SLO sampler) heartbeats the watchdog; the observatory samples
        # already-cached SLIs into bounded rings and evaluates
        # multi-window burn rates. GUBER_SLO_SAMPLE_INTERVAL=0 turns
        # both off (the watchdog without a sampler would flag stalls
        # nobody exports).
        self._watchdog = None
        self._slo = None
        if conf.slo_sample_interval_s > 0:
            from gubernator_tpu.runtime.watchdog import Watchdog
            from gubernator_tpu.service.slo import (
                SloObservatory,
                parse_slo_specs,
            )

            self._watchdog = Watchdog(stall_ms=conf.watchdog_stall_ms)
            # Injected attribute, checked per-iteration by the engine
            # loops — the engine threads started before the daemon
            # built the watchdog, and None keeps the engine usable
            # standalone (tests, tools) with zero overhead.
            self.engine.watchdog = self._watchdog
            self.svc.watchdog = self._watchdog
            if self._auditor is not None:
                self._auditor.watchdog = self._watchdog
            if self._lease_mgr is not None:
                self._lease_mgr.watchdog = self._watchdog
            if self._profiler is not None:
                self._profiler.watchdog = self._watchdog
            if self._standby is not None:
                self._standby.watchdog = self._watchdog
            self._slo = SloObservatory(
                self.svc,
                interval_s=conf.slo_sample_interval_s,
                specs=parse_slo_specs(conf.slo_specs),
                watchdog=self._watchdog,
            )
            self.svc.slo = self._slo
            self._watchdog.start()
            self._slo.start()

        # Overload control plane (docs/robustness.md "Overload control
        # & brownout"): the intake governor is injected into the engine
        # (deadline-aware bounded intake + CoDel tenant-fair shedding)
        # and the brownout ladder folds the SLO burn rates + watchdog
        # stall flags into a published degradation level. Off (default)
        # wires nothing — intake and forwarding stay bit-exact.
        self._overload = None
        if conf.overload:
            from gubernator_tpu.service.overload import (
                IntakeGovernor,
                OverloadManager,
            )

            governor = IntakeGovernor(
                limit=conf.intake_limit,
                target_ms=conf.intake_target_ms,
                metrics=self.svc.metrics,
                recorder=self.svc.recorder,
            )
            self._overload = OverloadManager(
                self.svc,
                governor,
                slo=self._slo,
                watchdog=self._watchdog,
            )
            self.svc.overload = self._overload
            # Injected attribute, checked per-call by intake and
            # per-pickup by the pump (same seam model as the watchdog).
            self.engine.overload = governor
            self._overload.start()

        # Discovery pool pushes membership through set_peers
        # (reference daemon.go:208-243). Unknown/unavailable backends fail
        # fast rather than silently serving as a cluster of one.
        from gubernator_tpu.service.discovery import DnsPool, StaticPool

        self._pool = None
        if conf.discovery == "dns":
            if not conf.dns_fqdn:
                raise ValueError("dns discovery requires GUBER_DNS_FQDN")
            self._pool = DnsPool(
                conf.dns_fqdn,
                self.set_peers,
                interval_s=conf.dns_interval_s,
                own_address=advertise,
                resolv_conf=conf.dns_resolv_conf,
            )
        elif conf.discovery == "static":
            if conf.peers:
                self._pool = StaticPool(conf.peers, self.set_peers)
        elif conf.discovery == "member-list":
            from gubernator_tpu.service.discovery import GossipPool

            self._pool = GossipPool(
                bind=conf.gossip_bind or "127.0.0.1:0",
                info=self.svc.local_info,
                on_update=self.set_peers,
                seeds=conf.gossip_seeds,
                interval_s=conf.gossip_interval_s,
                advertise=conf.gossip_advertise,
                secret=conf.gossip_secret,
            )
            await self._pool.started()  # resolve the ephemeral bind
        elif conf.discovery == "etcd":
            from gubernator_tpu.service.config import EtcdConfig
            from gubernator_tpu.service.etcd import EtcdPool

            econf = conf.etcd or EtcdConfig()
            if not econf.advertise_address:
                econf.advertise_address = advertise
            self._pool = EtcdPool(
                econf,
                PeerInfo(
                    grpc_address=econf.advertise_address,
                    http_address=self.http_address,
                    data_center=conf.data_center,
                ),
                self.set_peers,
            )
        elif conf.discovery == "k8s":
            from gubernator_tpu.service.config import K8sConfig
            from gubernator_tpu.service.k8s import K8sPool

            self._pool = K8sPool(conf.k8s or K8sConfig(), self.set_peers)
        else:
            raise ValueError(f"unknown peer discovery type: {conf.discovery!r}")

        # Readiness gate (reference WaitForConnect, daemon.go:451-488):
        # confirm every listener actually accepts connections before
        # declaring the daemon started.
        await self.wait_for_connect()

    async def wait_for_connect(self, timeout_s: float = 10.0) -> None:
        """Dial each listener until it accepts a TCP connection
        (reference daemon.go:451-488)."""
        addrs = [a for a in (self.grpc_address, self.http_address) if a]
        if self.status_address:
            addrs.append(self.status_address)
        deadline = asyncio.get_running_loop().time() + timeout_s
        for addr in addrs:
            host, port = addr.rsplit(":", 1)
            # Bracketed IPv6 hosts ("[::]:81" -> "[::]") must be unwrapped
            # before the wildcard check, or the dial below targets the
            # literal string "[::]" and times out.
            host = host.strip("[]")
            if host in ("0.0.0.0", "::"):
                host = "127.0.0.1"
            while True:
                try:
                    _, writer = await asyncio.open_connection(host, int(port))
                    writer.close()
                    break
                except OSError:
                    if asyncio.get_running_loop().time() > deadline:
                        raise TimeoutError(
                            f"listener {addr} not accepting connections "
                            f"after {timeout_s}s"
                        )
                    await asyncio.sleep(0.05)

    async def close(self) -> None:
        """Graceful drain, then teardown (docs/robustness.md "Rolling
        restarts & handover"). SIGTERM lands here via cmd/daemon.py; the
        sequence flips the node lossless instead of dropping in-flight
        traffic and resetting limits:

        1. DRAINING state: /readyz and HealthCheck report `draining`
           (orchestrators stop routing without killing the pod), and
           discovery deregisters so no new ownership lands here.
        2. Intake stops: the gRPC/edge listeners quit accepting new
           RPCs but in-flight calls get the drain budget to finish
           (the engine pump is still alive to serve them).
        3. Replication flush: queued GLOBAL hit-updates/broadcasts and
           MULTI_REGION legs ship now instead of dying with the loop.
        4. Ownership handover: every owned key's counter state ships to
           its ring successor over TransferSnapshots.
        5. Engine drain: the pump finishes its queue; only stragglers
           past GUBER_DRAIN_TIMEOUT fail, with the typed retryable
           status (api.types.ERR_ENGINE_DRAINING).
        6. Loader.save runs AFTER the engine drained, so the checkpoint
           includes every applied hit; then teardown."""
        if self.state == "stopped":
            return
        drain_s = max(float(getattr(self.conf, "drain_timeout_s", 5.0)), 0.0)
        self.state = "draining"
        if self.svc is not None:
            self.svc.draining = True
        # Auditor first: an audit RPC racing the drain would read peers
        # that are mid-handover and report phantom divergence.
        if getattr(self, "_auditor", None) is not None:
            await self._auditor.close()
        if getattr(self, "_profiler", None) is not None:
            self._profiler.stop()
        if getattr(self, "_host_probes", None) is not None:
            self._host_probes.stop()
        # Ladder before the SLO sampler it reads, then sampler +
        # watchdog before the loops they observe: a loop stopping
        # during drain must not be flagged as a stall. The engine keeps
        # its governor through drain — queued entries whose deadline
        # lapses mid-drain are still dropped at pickup.
        if getattr(self, "_overload", None) is not None:
            self._overload.stop()
        if getattr(self, "_slo", None) is not None:
            self._slo.stop()
        if getattr(self, "_watchdog", None) is not None:
            self._watchdog.stop()
        if getattr(self, "_pool", None) is not None:
            self._pool.close()
        # Standby before the listener stops AND before drain_handover:
        # the retire legs need peers' transports up, and retiring the
        # shadows first guarantees the standby and the handover never
        # both replay the same rows at a successor (docs/robustness.md
        # "Standby replication & crash recovery").
        if getattr(self, "_standby", None) is not None:
            await self._standby.close()
        # preStop settle (the k8s preStop-sleep analog): calls already on
        # the wire get dispatched to handlers before the listener stops
        # accepting — without it, transport-queued RPCs die CANCELLED at
        # stop() no matter how long the grace is.
        await asyncio.sleep(min(0.05, drain_s))
        if self.grpc_server is not None:
            # Stops new RPCs immediately; in-flight handlers get up to
            # the drain budget (the engine below them is still serving).
            await self.grpc_server.stop(grace=drain_s)
        if getattr(self, "edge_listener", None) is not None:
            await self.edge_listener.close()
        if self.svc is not None and self.svc.global_mgr is not None:
            await self.svc.global_mgr.drain()
        if self.svc is not None and getattr(self.svc, "region_mgr", None) is not None:
            await self.svc.region_mgr.drain()
        if self.svc is not None and hasattr(self.svc.forwarder, "drain_handover"):
            await self.svc.forwarder.drain_handover()
        if self.svc is not None and self.svc.global_mgr is not None:
            await self.svc.global_mgr.close()
        if self.svc is not None and getattr(self.svc, "region_mgr", None) is not None:
            await self.svc.region_mgr.close()
        # After drain_handover: the handover ships outstanding lease
        # records to ring successors, so the manager must outlive it.
        if getattr(self, "_lease_mgr", None) is not None:
            await self._lease_mgr.close()
        if self.engine is not None:
            # Engine close blocks for its own drain pass; keep the event
            # loop responsive (other in-process daemons share it).
            await asyncio.get_running_loop().run_in_executor(
                None, self.engine.close
            )
        # Checkpoint AFTER the engine drained (reference workerPool.Store
        # at shutdown, gubernator.go:151-178) so the snapshot includes
        # every hit the drain just applied.
        if self.conf.loader is not None and self.engine is not None:
            from gubernator_tpu.store import save_engine

            save_engine(self.engine, self.conf.loader)
        if self.svc is not None and self.svc.forwarder is not None:
            await self.svc.forwarder.close()
        if self._channel is not None:
            # Grace lets client-side RPCs that already have responses in
            # flight deliver them instead of dying CANCELLED.
            await self._channel.close(grace=drain_s)
            self._channel = None
        if self.http_runner is not None:
            await self.http_runner.cleanup()
        if getattr(self, "status_runner", None) is not None:
            await self.status_runner.cleanup()
        if getattr(self, "_host_probes", None) is not None:
            # Stopped at the drain's start: its thread ended long ago.
            self._host_probes.join()
            self._host_probes = None
        self.state = "stopped"

    # -- peers ---------------------------------------------------------------

    def set_peers(self, peers: Sequence[PeerInfo]) -> None:
        """Discovery callback (reference daemon.go:208-243 -> SetPeers)."""
        local = self.svc.local_info
        normalized: List[PeerInfo] = []
        for p in peers:
            # Self-detection: advertise-address equality, or a discovery
            # backend that already marked this entry as us (DnsPool).
            is_self = p.is_owner or p.grpc_address == local.grpc_address
            normalized.append(
                PeerInfo(
                    grpc_address=p.grpc_address,
                    http_address=p.http_address,
                    data_center=p.data_center,
                    is_owner=is_self,
                )
            )
        self.svc.set_peers(normalized)

    def peer_info(self) -> PeerInfo:
        return self.svc.local_info

    # -- client helper (reference daemon.go:433-447) -------------------------

    def client(self) -> rpc.V1Stub:
        if self._channel is None:
            if self.conf.tls is not None:
                from gubernator_tpu.service.tls import client_credentials

                target = self.grpc_address.replace("0.0.0.0", "localhost")
                self._channel = grpc.aio.secure_channel(
                    target,
                    client_credentials(self.conf.tls, client_cert=True),
                    options=(("grpc.ssl_target_name_override", "localhost"),),
                )
            else:
                self._channel = grpc.aio.insecure_channel(self.grpc_address)
        return rpc.V1Stub(self._channel)

    async def must_client(self) -> rpc.V1Stub:
        return self.client()
