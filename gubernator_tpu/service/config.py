"""Daemon configuration (reference config.go:73-252 analog).

Library users fill these dataclasses directly; the CLI/env layer
(`gubernator_tpu.service.envconfig`) populates them from GUBER_* env vars
the way the reference's SetupDaemonConfig does (config.go:270-479).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

from gubernator_tpu.api.types import PeerInfo
from gubernator_tpu.runtime.engine import EngineConfig


@dataclasses.dataclass
class BehaviorConfig:
    """Batching / GLOBAL tuning knobs (reference config.go:49-70,126-134)."""

    batch_timeout_s: float = 0.5
    batch_wait_s: float = 500e-6
    batch_limit: int = 1000

    global_timeout_s: float = 0.5
    global_sync_wait_s: float = 0.1
    global_batch_limit: int = 1000
    global_peer_requests_concurrency: int = 100

    force_global: bool = False
    # Forward every peer request as its own RPC instead of micro-batching
    # (reference Behaviors.DisableBatching / GUBER_DISABLE_BATCHING,
    # peer_client.go:128-133).
    disable_batching: bool = False

    # -- fault-domain knobs (docs/robustness.md; no reference analog: the
    # reference retries a dead owner 5x back-to-back with no backoff) ----

    # Per-call deadline budget for the forwarding path: retries share
    # this budget instead of multiplying per-leg timeouts. Propagated to
    # the owning peer via request metadata ("deadline_ms", absolute epoch
    # ms) so a re-forwarded item honors the original caller's remaining
    # time.
    forward_deadline_s: float = 2.0

    # GUBER_PEER_QUEUE: bound on each peer's forward batch queue (was a
    # hardcoded 1000). A full queue sheds with the typed retryable
    # overload error instead of blocking producers; size it to
    # batch_limit x the number of batches you are willing to buffer
    # toward one slow peer.
    peer_queue: int = 1000

    # GUBER_RETRY_BUDGET: token-bucket retry budget for the client and
    # edge relays (service/overload.py RetryBudget) — each first attempt
    # deposits this fraction of a token, each retry spends one, so
    # retries can never multiply offered load by more than 1 + budget.
    # 0 disables retries entirely under sustained failure.
    retry_budget: float = 0.1

    # Per-peer circuit breaker (utils/breaker.py): trip after this many
    # consecutive transport failures, hold open for an exponential
    # backoff (base doubling per consecutive trip, capped, ±10% jitter),
    # then admit `circuit_half_open_probes` trial calls.
    circuit_failure_threshold: int = 5
    circuit_open_base_s: float = 0.5
    circuit_open_max_s: float = 30.0
    circuit_half_open_probes: int = 1

    # What the forwarding path does when the owner's circuit is open
    # (GUBER_OWNER_UNREACHABLE): "error" fails fast; "local" answers
    # from local engine state (eventual-consistency caveats in
    # docs/robustness.md) and queues the hits for reconciliation with
    # the owner once its circuit closes.
    owner_unreachable: str = "error"

    # GLOBAL hit-update redelivery: a failed flush leg is merged back
    # into the hit queue instead of dropped. Each key survives at most
    # `global_requeue_limit` failed *send attempts* (circuit-open skips
    # do not age a key — no send was attempted), and at most
    # `global_requeue_max_keys` keys are held for redelivery; past
    # either cap, hits drop with the gubernator_global_send_dropped
    # counter.
    global_requeue_limit: int = 10
    global_requeue_max_keys: int = 10_000

    # Edge-tier frame-call timeout (GUBER_EDGE_TIMEOUT): was a
    # hard-coded 30.0 in EdgeClient.call.
    edge_timeout_s: float = 30.0

    # -- zero-loss elasticity (docs/robustness.md "Rolling restarts &
    # handover"; no reference analog: the reference accepts counter
    # loss whenever ownership moves) --------------------------------------

    # GUBER_HANDOVER: when the ring changes (or this node drains), ship
    # counter state for keys this node no longer owns to their new
    # owners over TransferSnapshots; receivers merge last-writer-wins on
    # stamp. Off restores the reference's lossy elasticity semantics.
    handover: bool = True
    # GUBER_HANDOVER_MAX_KEYS: cap on keys gathered per handover pass;
    # beyond it keys drop (counted in gubernator_handover_keys_dropped).
    handover_max_keys: int = 100_000
    # GUBER_HANDOVER_CHUNK: keys per TransferSnapshots RPC leg.
    handover_chunk: int = 512

    # -- consistency observatory (docs/monitoring.md "Consistency"; no
    # reference analog: the reference takes GLOBAL reconvergence on
    # faith) --------------------------------------------------------------

    # GUBER_CONSISTENCY_AUDIT_INTERVAL: cadence of the background
    # divergence auditor (samples owned GLOBAL keys, fetches one
    # replica's view over PeersV1.DebugInfo, classifies lag/lost/
    # conflict). 0 disables the auditor.
    consistency_audit_interval_s: float = 60.0
    # GUBER_CONSISTENCY_AUDIT_KEYS: max owned keys sampled per pass.
    consistency_audit_keys: int = 32

    # -- cooperative token leases (docs/architecture.md "Cooperative
    # leases"; no reference analog: every reference check costs an RPC) --

    # GUBER_LEASES: master switch. Off (default) keeps every path
    # bit-exact with the pre-lease daemon — no LeaseManager is wired, no
    # probe/carve checks run, snapshot chunks carry no lease rows.
    leases: bool = False
    # GUBER_LEASE_TTL: owner-side lease lifetime; the advertised holder
    # ttl is this minus the worst observed peer clock skew, and never
    # reaches past the bucket window's reset_time.
    lease_ttl_s: float = 2.0
    # GUBER_LEASE_FRACTION: max slice per grant as a fraction of the
    # key's limit — bounds one holder's share of the budget (and with
    # it the worst-case over-admission per holder per ttl).
    lease_fraction: float = 0.1
    # GUBER_LEASE_LOW_WATER: holders renew when the local slice falls
    # below this fraction of its granted size.
    lease_low_water: float = 0.25
    # GUBER_LEASE_MAX_KEYS: cap on outstanding lease records per owner
    # (grants reject past it) and on distinct leased keys per holder
    # cache.
    lease_max_keys: int = 4096
    # GUBER_LEASE_SWEEP_INTERVAL: cadence of the owner-side expiry sweep
    # that reclaims lapsed slices (conservation's `expired` term).
    lease_sweep_interval_s: float = 1.0

    # GUBER_RETRY_AFTER: server-suggested backoff — OVER_LIMIT responses
    # (leased and unleased) carry retry_after_ms derived from
    # reset_time. Off (default) keeps responses bit-exact with today;
    # on trades the columnar fast edge for the richer responses (only
    # the object path attaches metadata, service/fastpath.py).
    retry_after: bool = False

    # -- crash-tolerant ownership (docs/robustness.md "Standby
    # replication & crash recovery"; no reference analog: the reference
    # loses every counter an owner holds when the owner dies hard) --------

    # GUBER_STANDBY: owners continuously ship incremental snapshot
    # deltas of their dirtied keys to their ring successor(s); on owner
    # death the standby promotes the shadowed rows. Off restores
    # hard-kill counter loss (planned ring changes stay lossless via
    # handover) and keeps every serving path bit-exact with the
    # pre-standby daemon.
    standby: bool = True
    # GUBER_STANDBY_INTERVAL: delta ship cadence. The published loss
    # bound is "hits dirtied since the last acked ship", so this is the
    # durability/traffic tradeoff knob.
    standby_interval_s: float = 1.0
    # GUBER_STANDBY_FACTOR: distinct ring successors each key's state
    # is shadowed to (replication factor minus the owner itself).
    standby_factor: int = 1
    # GUBER_STANDBY_PROMOTE_AFTER: a standby promotes a dead owner's
    # shadow once that owner's circuit has been continuously open this
    # long (removal from the ring promotes immediately).
    standby_promote_after_s: float = 3.0
    # GUBER_STANDBY_ANTI_ENTROPY_INTERVAL: cadence of the per-region
    # digest exchange that re-ships mismatched regions (repairs deltas
    # lost to drops/partitions). 0 disables anti-entropy repair.
    standby_anti_entropy_interval_s: float = 10.0
    # GUBER_STANDBY_MAX_KEYS: cap on dirty keys gathered per ship pass
    # and on shadow rows held per upstream owner; beyond it the oldest
    # dirt stays pending (the loss bound keeps counting it).
    standby_max_keys: int = 100_000


@dataclasses.dataclass
class EtcdConfig:
    """etcd discovery settings (reference EtcdPoolConfig + GUBER_ETCD_*
    env block, config.go:380-404, etcd.go:42-80)."""

    endpoints: List[str] = dataclasses.field(
        default_factory=lambda: ["localhost:2379"]
    )
    key_prefix: str = "/gubernator-peers"
    advertise_address: str = ""
    data_center: str = ""
    dial_timeout_s: float = 5.0
    user: str = ""
    password: str = ""
    # TLS toward etcd (reference setupEtcdTLS, config.go:680-715)
    tls_enabled: bool = False
    tls_ca: str = ""
    tls_cert: str = ""
    tls_key: str = ""
    tls_skip_verify: bool = False
    # lease TTL driving registration keepalive (reference etcd.go:37)
    lease_ttl_s: float = 30.0


@dataclasses.dataclass
class K8sConfig:
    """Kubernetes discovery settings (reference K8sPoolConfig + GUBER_K8S_*
    env block, kubernetes.go:24-33, config.go:405-413)."""

    namespace: str = "default"
    pod_ip: str = ""
    pod_port: str = ""
    selector: str = ""  # label selector for the peer Endpoints/Pods
    mechanism: str = "endpoints"  # endpoints | pods
    api_server: str = ""  # default: in-cluster env/service account
    token_file: str = "/var/run/secrets/kubernetes.io/serviceaccount/token"
    ca_file: str = "/var/run/secrets/kubernetes.io/serviceaccount/ca.crt"


@dataclasses.dataclass
class DaemonConfig:
    grpc_listen_address: str = "127.0.0.1:0"
    http_listen_address: str = "127.0.0.1:0"
    advertise_address: str = ""  # defaults to the bound gRPC address
    data_center: str = ""

    # Counter capacity: total slots = cache_size rounded up to groups*ways
    # (reference default 50k items, config.go:139-140)
    cache_size: int = 50_000

    behaviors: BehaviorConfig = dataclasses.field(default_factory=BehaviorConfig)
    engine: Optional[EngineConfig] = None
    # The jax device this daemon's table lives on (None: the process
    # default). Set by hosts of several daemons in one process — the
    # in-process cluster puts daemon i on chip i — never from the
    # environment: one daemon process owns one chip anyway.
    device: Optional[object] = None

    # Static peer list (the in-process cluster fixture and tests use this;
    # discovery pools feed the same set_peers path)
    peers: List[PeerInfo] = dataclasses.field(default_factory=list)

    # GLOBAL sync transport: "grpc" (cross-host, reference-compatible) or
    # "ici" (multi-device collective mode: the daemon serves a whole
    # device mesh as one process; see runtime/ici_engine.py)
    global_mode: str = "grpc"
    ici: Optional[object] = None  # runtime.ici_engine.IciEngineConfig

    # Discovery backend: static | dns | etcd | k8s | member-list
    discovery: str = "static"
    dns_fqdn: str = ""
    dns_interval_s: float = 300.0
    dns_resolv_conf: str = "/etc/resolv.conf"  # reference GUBER_RESOLV_CONF
    # member-list (gossip) backend (reference memberlist.go knobs)
    gossip_bind: str = ""  # UDP host:port; port 0 = ephemeral
    gossip_advertise: str = ""  # reference GUBER_MEMBERLIST_ADVERTISE_ADDRESS
    gossip_seeds: List[str] = dataclasses.field(default_factory=list)
    gossip_interval_s: float = 1.0
    # Shared HMAC key authenticating gossip datagrams (memberlist
    # SecretKey analog; authenticates, does not encrypt). "" = off.
    gossip_secret: str = ""
    # etcd / k8s discovery blocks (populated by the matching env vars)
    etcd: Optional[EtcdConfig] = None
    k8s: Optional[K8sConfig] = None

    # gRPC server hardening (reference daemon.go:120-133): receive cap is
    # always 1MB like the reference; conn-age rotation is opt-in.
    grpc_max_conn_age_s: float = 0.0  # GUBER_GRPC_MAX_CONN_AGE_SEC; 0 = off

    # Separate health-only listener that never requests a client cert
    # (reference HTTPStatusListenAddress / GUBER_STATUS_HTTP_ADDRESS,
    # daemon.go:305-333). Only meaningful with TLS+mTLS configured.
    status_http_listen_address: str = ""

    # Edge-tier listener (GUBER_EDGE_LISTEN_ADDRESS): framed-RPC address
    # (unix:///path or host:port) where gubernator-tpu-edge processes
    # relay client calls (service/edge.py). Empty = disabled. No
    # reference analog — the edge tier is the TPU-native scale-out of
    # the serving path (the chip-owning process is singular; gRPC
    # termination scales horizontally).
    edge_listen_address: str = ""

    # Span verbosity: ERROR | INFO | DEBUG (reference GUBER_TRACING_LEVEL,
    # config.go:717-752 — INFO drops noisy per-peer/healthcheck spans).
    trace_level: str = "INFO"

    # Log settings (reference GUBER_LOG_LEVEL / GUBER_LOG_FORMAT /
    # GUBER_DEBUG; applied by the CLI entry point).
    log_level: str = "info"
    log_format: str = ""  # "json" or "" (text)
    debug: bool = False

    # Reference GUBER_WORKER_COUNT sizes its goroutine WorkerPool
    # (workers.go:125-147). The TPU engine has no worker shards — the
    # kernel replaces them — so this knob is accepted and recorded but
    # intentionally has no effect (documented N/A).
    worker_count: int = 0

    # Peer picker tuning (reference config.go:421-443). Default
    # fnv1a-mix (fnv1a + murmur fmix64 finalizer) for distribution
    # quality — bare FNV skews badly on sequential keys; "fnv1" is the
    # reference-compat opt-in for drop-in key->owner ring parity.
    peer_picker_hash: str = "fnv1a-mix"
    hash_replicas: int = 512

    # Optional TLS (service.tls.TlsConfig); None = plaintext
    tls: Optional[object] = None

    # Optional OS/runtime Prometheus collectors: ["os", "golang"]
    # (reference flags.go:19-57; 'golang' maps to the Python runtime)
    metric_flags: List[str] = dataclasses.field(default_factory=list)

    # Optional persistence plugins (gubernator_tpu.store protocols):
    # loader restores at startup / saves at close (reference
    # gubernator.go:138-148, 151-178); store enables read-through +
    # write-behind on the engine.
    loader: Optional[object] = None
    store: Optional[object] = None

    # Instance identity for logs/debugging (reference GUBER_INSTANCE_ID)
    instance_id: str = ""

    # Block startup until the kernel width-bucket ladder is compiled so
    # the first NO_BATCHING request gets a width-sized kernel instead of
    # a batch_size-wide dispatch (GUBER_PREWARM_BUCKETS; VERDICT r3 item
    # 7). Off by default: the serving path never JIT-compiles either
    # way, and warm restarts make this near-instant under the
    # persistent compile cache.
    prewarm_buckets: bool = False
    prewarm_timeout_s: float = 600.0

    # Graceful-drain budget (GUBER_DRAIN_TIMEOUT): bounds how long a
    # SIGTERM/close() waits for in-flight RPCs and the engine queue to
    # finish before stragglers fail with the typed retryable status.
    # Also feeds EngineConfig.drain_timeout_s for the pump's own drain
    # pass (docs/robustness.md "Rolling restarts & handover").
    drain_timeout_s: float = 5.0

    # Continuous-batching pipeline depth (GUBER_PIPELINE_DEPTH): max
    # engine flushes in flight at once — host encode of the next flush
    # overlaps device execution of the previous (docs/architecture.md
    # "Pipelined dispatch"). 1 = the serial pump (bit-exact decisions
    # either way); feeds EngineConfig/IciEngineConfig.pipeline_depth.
    pipeline_depth: int = 2

    # Request-lifecycle observability (docs/monitoring.md "Tracing the
    # pipeline" / "Hot keys"): GUBER_HOTKEYS_K bounds the top-K hot-key
    # sketch (0 = off); GUBER_STAGE_METADATA returns a per-response
    # stage_breakdown_us metadata entry (off: zero per-item cost);
    # GUBER_EXEMPLARS attaches flush-trace exemplars to the latency
    # histograms under OpenMetrics negotiation.
    hotkeys_k: int = 128
    stage_metadata: bool = False
    exemplars: bool = True

    # Table observatory (docs/monitoring.md "Table census"):
    # GUBER_TABLE_CENSUS_TTL caches the device census scan for this many
    # seconds (scrapes within the window reuse it — zero device work);
    # GUBER_TABLE_CENSUS_THRESHOLDS sets the cold-set idleness
    # multipliers (a slot is "cold at kx" when idle > k x its own
    # duration); GUBER_TABLE_CENSUS_HEATMAP sets how many group regions
    # the occupancy heatmap aggregates into (the future page axis).
    census_ttl_s: float = 5.0
    census_thresholds: tuple = (1, 4, 16)
    census_heatmap_width: int = 64

    # Admission observatory (docs/monitoring.md "Admission"):
    # GUBER_ADMISSION_TTL caches the device admission scan (ground-truth
    # admitted-vs-limit accounting) for this many seconds — scrapes of
    # /metrics and /debug/admission within the window reuse it, zero
    # device work; GUBER_ADMISSION_RING bounds the decision
    # flight-recorder ring (last N answers with path, status, key hash,
    # staleness, trace id).
    admission_ttl_s: float = 5.0
    admission_ring: int = 256

    # Paged slot table (docs/architecture.md "Paged table"):
    # GUBER_TABLE_PAGE_GROUPS > 0 carves the table into pages of that
    # many contiguous groups behind a device-resident indirection map,
    # keeping only GUBER_TABLE_PAGE_BUDGET pages in HBM (cold pages
    # demote to a host-DRAM tier). GUBER_TABLE_PAGE_DEMOTE_INTERVAL
    # paces the background demoter (0 = demand demotes only);
    # GUBER_TABLE_PAGE_FREE_TARGET is the free-frame headroom it keeps.
    # Default off: the flat table is bit-exact and has zero translation
    # overhead when the keyspace fits HBM.
    page_groups: int = 0
    page_budget: int = 0
    page_demote_interval_s: float = 2.0
    page_free_target: int = 1

    # SLO observatory + self-watchdog (docs/monitoring.md "SLOs & burn
    # rates"): GUBER_SLO_SAMPLE_INTERVAL paces the background SLI
    # sampler that feeds the time-series rings (0 = observatory off);
    # GUBER_SLO_SPECS overrides/extends the built-in SLO spec set
    # (JSON list, see service/slo.py); GUBER_WATCHDOG_STALL_MS is the
    # heartbeat-age bound past which a background loop is flagged
    # stalled (0 = watchdog off).
    slo_sample_interval_s: float = 5.0
    slo_specs: str = ""
    watchdog_stall_ms: float = 5000.0

    # -- overload control plane (docs/robustness.md "Overload control &
    # brownout"; service/overload.py) ------------------------------------

    # GUBER_OVERLOAD: master switch. Off (default) keeps intake,
    # forwarding, and every response bit-exact with the pre-overload
    # daemon — no governor is injected, the intake queue stays
    # effectively unbounded.
    overload: bool = False
    # GUBER_INTAKE_LIMIT: engine intake queue budget; past it, intake
    # resolves the typed retryable ERR_OVERLOADED (with retry_after_ms)
    # instead of queueing toward a timeout.
    intake_limit: int = 8192
    # GUBER_INTAKE_TARGET_MS: CoDel target for the intake queue-wait
    # signal — when the per-interval MINIMUM wait sustains above this,
    # the governor sheds probabilistically with per-tenant weighting.
    intake_target_ms: float = 20.0

    # Continuous profiling (docs/monitoring.md "Device resources"):
    # GUBER_PROFILE_INTERVAL > 0 starts a background sampler that takes
    # a GUBER_PROFILE_SECONDS-long jax.profiler capture each interval,
    # keeping the newest GUBER_PROFILE_KEEP trace dirs on disk
    # (service/profiler.py). Default off — captures cost real device
    # time and trace bytes; an explicit operator opt-in.
    profile_interval_s: float = 0.0
    profile_seconds: float = 0.5
    profile_keep: int = 8

    def engine_config(self) -> EngineConfig:
        if self.engine is not None:
            return self.engine
        ways = 8
        groups = 1
        while groups * ways < self.cache_size:
            groups <<= 1
        return EngineConfig(
            num_groups=groups,
            ways=ways,
            batch_wait_s=self.behaviors.batch_wait_s,
            batch_limit=self.behaviors.batch_limit,
            # Daemons serve the columnar edge; sized kernel buckets
            # compile in the background at boot.
            fast_buckets=True,
            device=self.device,
            layout="fused",
            hotkeys_k=self.hotkeys_k,
            stage_metadata=self.stage_metadata,
            exemplars=self.exemplars,
            drain_timeout_s=self.drain_timeout_s,
            pipeline_depth=self.pipeline_depth,
            census_ttl_s=self.census_ttl_s,
            census_thresholds=self.census_thresholds,
            census_heatmap_width=self.census_heatmap_width,
            admission_ttl_s=self.admission_ttl_s,
            page_groups=self.page_groups,
            page_budget=self.page_budget,
            page_demote_interval_s=self.page_demote_interval_s,
            page_free_target=self.page_free_target,
            # Handover and standby replication need routable
            # (string-keyed) snapshots even on the store-less columnar
            # edge; with both off, skip the decode.
            record_columnar_keys=self.behaviors.handover
            or self.behaviors.standby,
        )
