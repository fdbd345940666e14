"""Environment-driven daemon configuration (reference config.go:270-479).

Same model as the reference: an optional `--config file` of KEY=VALUE
lines is injected into the environment first, then ~GUBER_* variables are
read with defaults (reference config.go:268-283, 633-658). Library users
skip this entirely and fill DaemonConfig directly.

Duration values accept Go-style suffixes (ns/us/ms/s/m/h) like the
reference's `500ms` / `500ns` examples in example.conf.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional

from gubernator_tpu.api.types import PeerInfo
from gubernator_tpu.service.config import (
    BehaviorConfig,
    DaemonConfig,
    EtcdConfig,
    K8sConfig,
)
from gubernator_tpu.service.tls import TlsConfig

_DUR_RE = re.compile(r"([0-9.]+)(ns|us|µs|ms|s|m|h)")
_DUR_SCALE = {
    "ns": 1e-9,
    "us": 1e-6,
    "µs": 1e-6,
    "ms": 1e-3,
    "s": 1.0,
    "m": 60.0,
    "h": 3600.0,
}


def parse_duration_s(v: str, default: float) -> float:
    """Go-style duration string -> seconds."""
    v = v.strip()
    if not v:
        return default
    total, matched = 0.0, False
    for m in _DUR_RE.finditer(v):
        total += float(m.group(1)) * _DUR_SCALE[m.group(2)]
        matched = True
    if not matched:
        try:
            return float(v)
        except ValueError:
            return default
    return total


def load_config_file(path: str) -> None:
    """Inject KEY=VALUE lines into the environment (values already set in
    the env win, matching the reference's precedence)."""
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#") or "=" not in line:
                continue
            k, v = line.split("=", 1)
            os.environ.setdefault(k.strip(), v.strip())


def _env(name: str, default: str = "") -> str:
    return os.environ.get(name, default)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _env_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _parse_census_thresholds(v: str) -> tuple:
    """GUBER_TABLE_CENSUS_THRESHOLDS: comma-separated idleness
    multipliers for the census cold-set table (e.g. "1,4,16")."""
    v = v.strip()
    if not v:
        return (1, 4, 16)
    try:
        out = tuple(int(p.strip()) for p in v.split(",") if p.strip())
    except ValueError:
        out = ()
    if not out or any(k < 1 for k in out):
        raise ValueError(
            f"'GUBER_TABLE_CENSUS_THRESHOLDS={v}' is invalid; expected "
            "comma-separated positive integers, e.g. '1,4,16'"
        )
    return out


# GUBER_<name> knobs that chose among programs a daemon no longer has:
# (name, the one value that still says what runs). A start that asks for
# anything else is refused, not served from a different program.
_RETIRED = (
    ("KERNEL", "xla"),
    ("TABLE_LAYOUT", "fused"),
    ("ICI_LAYOUT", "fused"),
    ("PALLAS_BLOCK", ""),
    ("PALLAS_INTERPRET", ""),
    ("PALLAS_TUNE", ""),
    ("PALLAS_TUNE_CACHE", ""),
)


def setup_daemon_config(config_file: Optional[str] = None) -> DaemonConfig:
    if config_file:
        load_config_file(config_file)
    for name, only in _RETIRED:
        v = _env("GUBER_" + name)
        if v.strip().lower() not in ("", only):
            raise ValueError(
                f"'GUBER_{name}={v}' is invalid; the knob is retired (a "
                "daemon serves the fused table layout under XLA and "
                "nothing selects another): unset it"
            )

    behaviors = BehaviorConfig(
        batch_timeout_s=parse_duration_s(_env("GUBER_BATCH_TIMEOUT"), 0.5),
        batch_wait_s=parse_duration_s(_env("GUBER_BATCH_WAIT"), 500e-6),
        batch_limit=_env_int("GUBER_BATCH_LIMIT", 1000),
        global_timeout_s=parse_duration_s(_env("GUBER_GLOBAL_TIMEOUT"), 0.5),
        global_sync_wait_s=parse_duration_s(_env("GUBER_GLOBAL_SYNC_WAIT"), 0.1),
        global_batch_limit=_env_int("GUBER_GLOBAL_BATCH_LIMIT", 1000),
        global_peer_requests_concurrency=_env_int(
            "GUBER_GLOBAL_PEER_REQUESTS_CONCURRENCY", 100
        ),
        force_global=_env_bool("GUBER_FORCE_GLOBAL"),
        disable_batching=_env_bool("GUBER_DISABLE_BATCHING"),
        # Fault-domain knobs (docs/robustness.md)
        forward_deadline_s=parse_duration_s(_env("GUBER_FORWARD_DEADLINE"), 2.0),
        circuit_failure_threshold=_env_int("GUBER_CIRCUIT_FAILURE_THRESHOLD", 5),
        circuit_open_base_s=parse_duration_s(_env("GUBER_CIRCUIT_OPEN_BASE"), 0.5),
        circuit_open_max_s=parse_duration_s(_env("GUBER_CIRCUIT_OPEN_MAX"), 30.0),
        circuit_half_open_probes=_env_int("GUBER_CIRCUIT_HALF_OPEN_PROBES", 1),
        owner_unreachable=_env("GUBER_OWNER_UNREACHABLE", "error").lower(),
        peer_queue=_env_int("GUBER_PEER_QUEUE", 1000),
        retry_budget=_env_float("GUBER_RETRY_BUDGET", 0.1),
        global_requeue_limit=_env_int("GUBER_GLOBAL_REQUEUE_LIMIT", 10),
        global_requeue_max_keys=_env_int("GUBER_GLOBAL_REQUEUE_MAX_KEYS", 10_000),
        edge_timeout_s=parse_duration_s(_env("GUBER_EDGE_TIMEOUT"), 30.0),
        # Zero-loss elasticity (docs/robustness.md "Rolling restarts &
        # handover"): GUBER_HANDOVER=off restores the reference's lossy
        # ownership-move semantics.
        handover=_env_bool("GUBER_HANDOVER", True),
        handover_max_keys=_env_int("GUBER_HANDOVER_MAX_KEYS", 100_000),
        handover_chunk=_env_int("GUBER_HANDOVER_CHUNK", 512),
        # Consistency observatory (docs/monitoring.md "Consistency"):
        # divergence-auditor cadence and sample size; interval 0 disables.
        consistency_audit_interval_s=parse_duration_s(
            _env("GUBER_CONSISTENCY_AUDIT_INTERVAL"), 60.0
        ),
        consistency_audit_keys=_env_int("GUBER_CONSISTENCY_AUDIT_KEYS", 32),
        # Cooperative token leases (docs/architecture.md "Cooperative
        # leases"): GUBER_LEASES off keeps every path bit-exact with the
        # pre-lease daemon.
        leases=_env_bool("GUBER_LEASES"),
        lease_ttl_s=parse_duration_s(_env("GUBER_LEASE_TTL"), 2.0),
        lease_fraction=_env_float("GUBER_LEASE_FRACTION", 0.1),
        lease_low_water=_env_float("GUBER_LEASE_LOW_WATER", 0.25),
        lease_max_keys=_env_int("GUBER_LEASE_MAX_KEYS", 4096),
        lease_sweep_interval_s=parse_duration_s(
            _env("GUBER_LEASE_SWEEP_INTERVAL"), 1.0
        ),
        # Server-suggested backoff (ROADMAP item 3 first step).
        retry_after=_env_bool("GUBER_RETRY_AFTER"),
        # Crash-tolerant ownership (docs/robustness.md "Standby
        # replication & crash recovery"): GUBER_STANDBY=0 restores
        # hard-kill counter loss and is bit-exact with the pre-standby
        # daemon.
        standby=_env_bool("GUBER_STANDBY", True),
        standby_interval_s=parse_duration_s(
            _env("GUBER_STANDBY_INTERVAL"), 1.0
        ),
        standby_factor=_env_int("GUBER_STANDBY_FACTOR", 1),
        standby_promote_after_s=parse_duration_s(
            _env("GUBER_STANDBY_PROMOTE_AFTER"), 3.0
        ),
        standby_anti_entropy_interval_s=parse_duration_s(
            _env("GUBER_STANDBY_ANTI_ENTROPY_INTERVAL"), 10.0
        ),
        standby_max_keys=_env_int("GUBER_STANDBY_MAX_KEYS", 100_000),
    )
    if behaviors.standby:
        if behaviors.standby_interval_s <= 0:
            raise ValueError(
                f"'GUBER_STANDBY_INTERVAL={behaviors.standby_interval_s}' "
                "is invalid; expected a positive duration"
            )
        if behaviors.standby_factor < 1:
            raise ValueError(
                f"'GUBER_STANDBY_FACTOR={behaviors.standby_factor}' is "
                "invalid; expected a positive successor count"
            )
        if behaviors.standby_promote_after_s <= 0:
            raise ValueError(
                "'GUBER_STANDBY_PROMOTE_AFTER="
                f"{behaviors.standby_promote_after_s}' is invalid; "
                "expected a positive duration"
            )
    if not (0.0 < behaviors.lease_fraction <= 1.0):
        raise ValueError(
            f"'GUBER_LEASE_FRACTION={behaviors.lease_fraction}' is "
            "invalid; expected a fraction in (0, 1]"
        )
    if behaviors.owner_unreachable not in ("error", "local"):
        raise ValueError(
            f"'GUBER_OWNER_UNREACHABLE={behaviors.owner_unreachable}' is "
            "invalid; choices are [error, local]"
        )
    if behaviors.peer_queue < 1:
        raise ValueError(
            f"'GUBER_PEER_QUEUE={behaviors.peer_queue}' is invalid; the "
            "peer forward queue must hold at least 1 entry"
        )
    if not (0.0 <= behaviors.retry_budget <= 1.0):
        raise ValueError(
            f"'GUBER_RETRY_BUDGET={behaviors.retry_budget}' is invalid; "
            "expected a fraction in [0, 1] (0 disables retries under "
            "sustained failure)"
        )

    conf = DaemonConfig(
        instance_id=_env("GUBER_INSTANCE_ID", ""),
        grpc_listen_address=_env("GUBER_GRPC_ADDRESS", "127.0.0.1:81"),
        http_listen_address=_env("GUBER_HTTP_ADDRESS", "127.0.0.1:80"),
        status_http_listen_address=_env("GUBER_STATUS_HTTP_ADDRESS", ""),
        edge_listen_address=_env("GUBER_EDGE_LISTEN_ADDRESS", ""),
        advertise_address=_env("GUBER_ADVERTISE_ADDRESS", ""),
        data_center=_env("GUBER_DATA_CENTER", ""),
        cache_size=_env_int("GUBER_CACHE_SIZE", 50_000),
        behaviors=behaviors,
        global_mode=_env("GUBER_GLOBAL_MODE", "grpc"),
        grpc_max_conn_age_s=float(_env_int("GUBER_GRPC_MAX_CONN_AGE_SEC", 0)),
        trace_level=_env("GUBER_TRACING_LEVEL", "INFO").upper(),
        log_level=_env("GUBER_LOG_LEVEL", "info"),
        log_format=_env("GUBER_LOG_FORMAT", ""),
        debug=_env_bool("GUBER_DEBUG"),
        # Sizes the reference's goroutine pool; N/A for the device engine
        # (see DaemonConfig.worker_count).
        worker_count=_env_int("GUBER_WORKER_COUNT", 0),
        # Block startup on the width-bucket compile ladder (config.py
        # prewarm_buckets docs; ADVICE r4: these were documented but
        # never read from the environment).
        prewarm_buckets=_env_bool("GUBER_PREWARM_BUCKETS"),
        prewarm_timeout_s=parse_duration_s(_env("GUBER_PREWARM_TIMEOUT"), 600.0),
        # SIGTERM drain budget (docs/robustness.md): in-flight RPCs, the
        # engine queue, replication flushes, and the ownership handover
        # all finish inside this window before teardown.
        drain_timeout_s=parse_duration_s(_env("GUBER_DRAIN_TIMEOUT"), 5.0),
        # Continuous-batching pipeline depth (docs/architecture.md
        # "Pipelined dispatch"): 1 = serial pump, >=2 overlaps host
        # encode with device execution. Decisions are bit-exact across
        # depths.
        pipeline_depth=_env_int("GUBER_PIPELINE_DEPTH", 2),
        # Request-lifecycle observability (docs/monitoring.md): hot-key
        # sketch size, per-response stage breakdown, histogram exemplars.
        hotkeys_k=_env_int("GUBER_HOTKEYS_K", 128),
        stage_metadata=_env_bool("GUBER_STAGE_METADATA"),
        exemplars=_env_bool("GUBER_EXEMPLARS", True),
        # Table observatory (docs/monitoring.md "Table census"): census
        # scan TTL, cold-set idleness multipliers, heatmap region count.
        census_ttl_s=parse_duration_s(_env("GUBER_TABLE_CENSUS_TTL"), 5.0),
        census_thresholds=_parse_census_thresholds(
            _env("GUBER_TABLE_CENSUS_THRESHOLDS")
        ),
        census_heatmap_width=_env_int("GUBER_TABLE_CENSUS_HEATMAP", 64),
        # Admission observatory (docs/monitoring.md "Admission"):
        # admission-scan TTL and decision flight-recorder ring size.
        admission_ttl_s=parse_duration_s(_env("GUBER_ADMISSION_TTL"), 5.0),
        admission_ring=_env_int("GUBER_ADMISSION_RING", 256),
        # Paged slot table (docs/architecture.md "Paged table"): page
        # granularity in groups (0 = flat table), resident-page budget,
        # background-demoter cadence, and free-frame headroom target.
        page_groups=_env_int("GUBER_TABLE_PAGE_GROUPS", 0),
        page_budget=_env_int("GUBER_TABLE_PAGE_BUDGET", 0),
        page_demote_interval_s=parse_duration_s(
            _env("GUBER_TABLE_PAGE_DEMOTE_INTERVAL"), 2.0
        ),
        page_free_target=_env_int("GUBER_TABLE_PAGE_FREE_TARGET", 1),
        # SLO observatory + self-watchdog (docs/monitoring.md "SLOs &
        # burn rates"): SLI sampler cadence (0 = off), SLO spec
        # override JSON, heartbeat stall bound (0 = watchdog off).
        slo_sample_interval_s=parse_duration_s(
            _env("GUBER_SLO_SAMPLE_INTERVAL"), 5.0
        ),
        slo_specs=_env("GUBER_SLO_SPECS"),
        watchdog_stall_ms=_env_float("GUBER_WATCHDOG_STALL_MS", 5000.0),
        # Overload control plane (docs/robustness.md "Overload control
        # & brownout"): master switch (off = bit-exact), intake queue
        # budget, CoDel queue-wait target.
        overload=_env_bool("GUBER_OVERLOAD"),
        intake_limit=_env_int("GUBER_INTAKE_LIMIT", 8192),
        intake_target_ms=_env_float("GUBER_INTAKE_TARGET_MS", 20.0),
        # Continuous profiling (docs/monitoring.md "Device resources"):
        # sampler cadence (0 = off), per-capture trace length, and how
        # many trace dirs the rotation keeps.
        profile_interval_s=parse_duration_s(
            _env("GUBER_PROFILE_INTERVAL"), 0.0
        ),
        profile_seconds=parse_duration_s(_env("GUBER_PROFILE_SECONDS"), 0.5),
        profile_keep=_env_int("GUBER_PROFILE_KEEP", 8),
    )
    if conf.profile_keep < 1:
        raise ValueError(
            f"'GUBER_PROFILE_KEEP={conf.profile_keep}' is invalid; the "
            "rotation must keep at least 1 trace"
        )
    if conf.slo_sample_interval_s < 0:
        raise ValueError(
            f"'GUBER_SLO_SAMPLE_INTERVAL={conf.slo_sample_interval_s}' is "
            "invalid; must be >= 0 (0 disables the SLO observatory)"
        )
    if conf.watchdog_stall_ms < 0:
        raise ValueError(
            f"'GUBER_WATCHDOG_STALL_MS={conf.watchdog_stall_ms}' is "
            "invalid; must be >= 0 (0 disables the watchdog)"
        )
    if conf.slo_specs:
        # Fail a malformed GUBER_SLO_SPECS at config time, not at first
        # observatory tick (spec shape errors included).
        from gubernator_tpu.service.slo import parse_slo_specs

        try:
            parse_slo_specs(conf.slo_specs)
        except ValueError as e:
            raise ValueError(f"'GUBER_SLO_SPECS' is invalid: {e}") from None
    if conf.intake_limit < 1:
        raise ValueError(
            f"'GUBER_INTAKE_LIMIT={conf.intake_limit}' is invalid; the "
            "intake budget must admit at least 1 queued entry"
        )
    if conf.intake_target_ms <= 0:
        raise ValueError(
            f"'GUBER_INTAKE_TARGET_MS={conf.intake_target_ms}' is "
            "invalid; the CoDel target must be a positive duration"
        )
    if conf.admission_ring < 1:
        raise ValueError(
            f"'GUBER_ADMISSION_RING={conf.admission_ring}' is invalid; "
            "the decision flight recorder must hold at least 1 entry"
        )
    if conf.census_heatmap_width < 1:
        raise ValueError(
            f"'GUBER_TABLE_CENSUS_HEATMAP={conf.census_heatmap_width}' is "
            "invalid; must be >= 1 heatmap region"
        )
    if conf.pipeline_depth < 1:
        raise ValueError(
            f"'GUBER_PIPELINE_DEPTH={conf.pipeline_depth}' is invalid; "
            "must be >= 1 (1 = serial dispatch)"
        )
    if conf.page_groups < 0:
        raise ValueError(
            f"'GUBER_TABLE_PAGE_GROUPS={conf.page_groups}' is invalid; "
            "must be >= 0 (0 disables table paging)"
        )
    if conf.page_groups > 0 and conf.page_budget < 1:
        raise ValueError(
            f"'GUBER_TABLE_PAGE_BUDGET={conf.page_budget}' is invalid; "
            "must be >= 1 resident page when GUBER_TABLE_PAGE_GROUPS "
            "enables paging"
        )

    # ICI-mode sizing (GUBER_GLOBAL_MODE=ici): the replica table must be
    # sized so live GLOBAL keys per group stay <= replica ways, or keys
    # degrade to per-replica counting (docs/architecture.md "Overflow
    # and drift bounds"). Analog of the reference's GUBER_CACHE_SIZE for
    # the collective tier.
    if conf.global_mode == "ici":
        # Always built in ici mode (not only when a GUBER_ICI_* sizing
        # var is present), or GUBER_BATCH_WAIT / GUBER_BATCH_LIMIT /
        # GUBER_GLOBAL_SYNC_WAIT would silently fall back to dataclass
        # defaults in an env-sized-by-default deployment.
        from gubernator_tpu.runtime.ici_engine import IciEngineConfig

        base = IciEngineConfig()
        conf.ici = IciEngineConfig(
            num_groups=_env_int("GUBER_ICI_NUM_GROUPS", base.num_groups),
            ways=_env_int("GUBER_ICI_WAYS", base.ways),
            num_slots=_env_int("GUBER_ICI_NUM_SLOTS", base.num_slots),
            replica_ways=_env_int(
                "GUBER_ICI_REPLICA_WAYS", base.replica_ways
            ),
            # the collective tick honors GlobalSyncWait like the gRPC
            # tier, and the micro-batch pump honors GUBER_BATCH_* (ADVICE
            # r4: these were silently reset to dataclass defaults).
            sync_wait_s=behaviors.global_sync_wait_s,
            batch_wait_s=behaviors.batch_wait_s,
            batch_limit=behaviors.batch_limit,
            pipeline_depth=conf.pipeline_depth,
            hotkeys_k=conf.hotkeys_k,
            stage_metadata=conf.stage_metadata,
            exemplars=conf.exemplars,
            census_ttl_s=conf.census_ttl_s,
            census_thresholds=conf.census_thresholds,
            census_heatmap_width=conf.census_heatmap_width,
            # 0 = unbounded (merge the full table every tick)
            max_sync_groups=(
                _env_int("GUBER_ICI_SYNC_GROUPS", base.max_sync_groups or 0)
                or None
            ),
            # Fingerprint-collision backstop for the capped tick: force
            # one full-table tick every N capped ticks (0 = off).
            full_tick_every=_env_int(
                "GUBER_ICI_FULL_TICK_EVERY", base.full_tick_every
            ),
            # Paged sharded tier: same GUBER_TABLE_PAGE_* knobs as the
            # single-chip engine (the unified core pages both; the page
            # map replicates across the mesh, frames shard, and each
            # shard runs its own pool + host-DRAM cold tier).
            page_groups=conf.page_groups,
            page_budget=conf.page_budget,
            page_demote_interval_s=conf.page_demote_interval_s,
            page_free_target=conf.page_free_target,
        )

    # Static peers: GUBER_STATIC_PEERS=grpc1|http1|dc1,grpc2|http2|dc2
    static = _env("GUBER_STATIC_PEERS")
    if static:
        peers: List[PeerInfo] = []
        for part in static.split(","):
            fields = part.split("|")
            peers.append(
                PeerInfo(
                    grpc_address=fields[0],
                    http_address=fields[1] if len(fields) > 1 else "",
                    data_center=fields[2] if len(fields) > 2 else "",
                )
            )
        conf.peers = peers

    conf.discovery = _env("GUBER_PEER_DISCOVERY_TYPE", "static")
    conf.dns_fqdn = _env("GUBER_DNS_FQDN", "")
    conf.dns_interval_s = parse_duration_s(_env("GUBER_DNS_POLL_INTERVAL"), 300.0)
    conf.dns_resolv_conf = _env("GUBER_RESOLV_CONF", "/etc/resolv.conf")
    # member-list / gossip (reference GUBER_MEMBERLIST_* envs)
    conf.gossip_bind = _env("GUBER_MEMBERLIST_ADDRESS", "")
    conf.gossip_advertise = _env("GUBER_MEMBERLIST_ADVERTISE_ADDRESS", "")
    known = _env("GUBER_MEMBERLIST_KNOWN_NODES", "")
    conf.gossip_seeds = [n.strip() for n in known.split(",") if n.strip()]
    conf.gossip_interval_s = parse_duration_s(
        _env("GUBER_MEMBERLIST_GOSSIP_INTERVAL"), 1.0
    )
    conf.gossip_secret = _env("GUBER_MEMBERLIST_SECRET_KEY", "")
    if conf.discovery == "member-list" and not conf.gossip_seeds:
        raise ValueError(
            "when using `member-list` for peer discovery, you MUST provide a "
            "hostname of a known host in the cluster via "
            "`GUBER_MEMBERLIST_KNOWN_NODES`"
        )

    # etcd block (reference GUBER_ETCD_*, config.go:380-404; the reference
    # also accepts the misspelled GUBER_ETCD_TLS_EABLED, config.go:701)
    if conf.discovery == "etcd" or any(
        k.startswith("GUBER_ETCD_") for k in os.environ
    ):
        endpoints = _env("GUBER_ETCD_ENDPOINTS", "localhost:2379")
        conf.etcd = EtcdConfig(
            endpoints=[e.strip() for e in endpoints.split(",") if e.strip()],
            key_prefix=_env("GUBER_ETCD_KEY_PREFIX", "/gubernator-peers"),
            advertise_address=_env(
                "GUBER_ETCD_ADVERTISE_ADDRESS", conf.advertise_address
            ),
            data_center=_env("GUBER_ETCD_DATA_CENTER", conf.data_center),
            dial_timeout_s=parse_duration_s(_env("GUBER_ETCD_DIAL_TIMEOUT"), 5.0),
            user=_env("GUBER_ETCD_USER", ""),
            password=_env("GUBER_ETCD_PASSWORD", ""),
            tls_enabled=_env_bool("GUBER_ETCD_TLS_ENABLE")
            or _env_bool("GUBER_ETCD_TLS_ENABLED")
            or _env_bool("GUBER_ETCD_TLS_EABLED"),  # reference's misspelling
            tls_ca=_env("GUBER_ETCD_TLS_CA", ""),
            tls_cert=_env("GUBER_ETCD_TLS_CERT", ""),
            tls_key=_env("GUBER_ETCD_TLS_KEY", ""),
            tls_skip_verify=_env_bool("GUBER_ETCD_TLS_SKIP_VERIFY"),
        )

    # k8s block (reference GUBER_K8S_*, config.go:405-413 + selector
    # validation :445-449)
    if conf.discovery == "k8s" or any(
        k.startswith("GUBER_K8S_") for k in os.environ
    ):
        mech = _env("GUBER_K8S_WATCH_MECHANISM", "endpoints") or "endpoints"
        if mech not in ("endpoints", "pods"):
            raise ValueError(
                "invalid value for watch mechanism `GUBER_K8S_WATCH_MECHANISM` "
                "needs to be either 'endpoints' or 'pods' (defaults to "
                "'endpoints')"
            )
        conf.k8s = K8sConfig(
            namespace=_env("GUBER_K8S_NAMESPACE", "default"),
            pod_ip=_env("GUBER_K8S_POD_IP", ""),
            pod_port=_env("GUBER_K8S_POD_PORT", ""),
            selector=_env("GUBER_K8S_ENDPOINTS_SELECTOR", ""),
            mechanism=mech,
        )
        if conf.discovery == "k8s" and not conf.k8s.selector:
            raise ValueError(
                "when using k8s for peer discovery, you MUST provide a "
                "`GUBER_K8S_ENDPOINTS_SELECTOR` to select the gubernator "
                "peers from the endpoints listing"
            )

    # Peer picker (reference config.go:421-443): GUBER_PEER_PICKER selects
    # the implementation (only replicated-hash exists). The hash defaults
    # to fnv1a-mix for distribution quality (bare FNV skews badly on
    # sequential keys); set GUBER_PEER_PICKER_HASH=fnv1 ONLY for
    # drop-in key->owner parity with a live reference cluster.
    picker = _env("GUBER_PEER_PICKER", "")
    if picker and picker != "replicated-hash":
        raise ValueError(
            f"'GUBER_PEER_PICKER={picker}' is invalid; choices are "
            "['replicated-hash', 'consistent-hash']"
        )
    conf.peer_picker_hash = _env("GUBER_PEER_PICKER_HASH", "fnv1a-mix")
    if conf.peer_picker_hash not in ("fnv1", "fnv1a", "fnv1a-mix"):
        raise ValueError(
            f"'GUBER_PEER_PICKER_HASH={conf.peer_picker_hash}' is invalid; "
            "choices are [fnv1, fnv1a, fnv1a-mix]"
        )
    conf.hash_replicas = _env_int("GUBER_REPLICATED_HASH_REPLICAS", 512)

    # Optional process/runtime collectors (reference flags.go:19-57,
    # GUBER_METRIC_FLAGS=os,golang; 'golang' maps to Python runtime/GC)
    conf.metric_flags = [
        f.strip() for f in _env("GUBER_METRIC_FLAGS").split(",") if f.strip()
    ]

    import ssl as _ssl

    # Reference getEnvMinVersion (config.go:580-597): "1.0"-"1.3", unknown
    # values fall back to the highest supported version.
    min_map = {
        "": _ssl.TLSVersion.TLSv1_3,  # reference default when unset
        "1.0": _ssl.TLSVersion.TLSv1,
        "1.1": _ssl.TLSVersion.TLSv1_1,
        "1.2": _ssl.TLSVersion.TLSv1_2,
        "1.3": _ssl.TLSVersion.TLSv1_3,
    }
    tls = TlsConfig(
        ca_file=_env("GUBER_TLS_CA"),
        ca_key_file=_env("GUBER_TLS_CA_KEY"),
        cert_file=_env("GUBER_TLS_CERT"),
        key_file=_env("GUBER_TLS_KEY"),
        auto_tls=_env_bool("GUBER_TLS_AUTO"),
        client_auth_ca_file=_env("GUBER_TLS_CLIENT_AUTH_CA_CERT"),
        client_auth_cert_file=_env("GUBER_TLS_CLIENT_AUTH_CERT"),
        client_auth_key_file=_env("GUBER_TLS_CLIENT_AUTH_KEY"),
        client_auth_server_name=_env("GUBER_TLS_CLIENT_AUTH_SERVER_NAME"),
        client_auth={
            "": "none",
            "request": "request",
            "require": "require",
            "require-and-verify": "require",
        }.get(_env("GUBER_TLS_CLIENT_AUTH"), "none"),
        insecure_skip_verify=_env_bool("GUBER_TLS_INSECURE_SKIP_VERIFY"),
        min_version=min_map.get(
            _env("GUBER_TLS_MIN_VERSION").strip(), _ssl.TLSVersion.TLSv1_3
        ),
    )
    conf.tls = (
        tls
        if (tls.ca_file or tls.cert_file or tls.auto_tls)
        else None
    )
    return conf
