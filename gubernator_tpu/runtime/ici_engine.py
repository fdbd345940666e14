"""IciEngine: the unified engine core served over a multi-device mesh.

IciEngine IS MeshEngine (runtime/engine.py) bound to the mesh topology
strategy (runtime/topology.py IciMeshTopology): the pump, pipeline ring,
ticket lifecycle, failure recovery, drain, snapshots, and census /
admission caching are the single core's — this file adds only what is
genuinely ici-specific policy: the GLOBAL sync *cadence* (background
tick thread + overflow/backlog counters) and the replica-targeted
`inject_globals`. It replaces the host-level peer mesh *inside* the
process (SURVEY.md §2.3):

- Non-GLOBAL traffic runs through the owner-sharded decide
  (parallel/mesh.py): the table shards across devices, one SPMD call per
  wave answers every lane at its owner. This is the collective analog of
  peer forwarding.
- GLOBAL traffic runs through per-device replicas (parallel/ici.py):
  lanes are assigned a home device round-robin (modeling which "node"
  the request hit), answered locally from that device's replica, and a
  background sync thread runs the collective delta/rebroadcast tick on
  the GlobalSyncWait cadence — the globalManager with psums instead of
  gRPC.
- The paged table works here exactly as on one chip: the mesh kernel
  facade keeps the physical frames sharded and the page map replicated,
  and the Pager runs one frame pool + host-DRAM cold tier PER SHARD
  (docs/architecture.md "Paged table").

The public surface matches DeviceEngine (check_async/check_bulk/
check_batch/close/inject_globals/snapshot/restore), so V1Service and the
daemon can use either; a daemon configured with global_mode="ici" serves
a whole pod as one process with no intra-pod RPCs.

Wave rules differ per path: sharded lanes split on slot-group conflicts
(scatter disjointness per device); replica lanes split on (home, group)
conflicts (same key on the same replica must serialize, but the same key
on different replicas is exactly multi-node GLOBAL behavior and may
share a wave).

guberlint GL013 (engine-core-drift) ratchets this file: a method here
whose name shadows a MeshEngine core method needs an explicit pragma —
the dispatch/complete/recovery logic must never re-fork.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Optional

import jax
import numpy as np

from gubernator_tpu.api.keys import key_hash128_batch
from gubernator_tpu.runtime.engine import MeshEngine, _WaveAssembler
from gubernator_tpu.runtime.topology import IciMeshTopology
from gubernator_tpu.runtime import telemetry as _telemetry
from gubernator_tpu.utils import clock as _clock
from gubernator_tpu.utils import tracing
from gubernator_tpu.utils import transfer as _transfer

log = logging.getLogger("gubernator_tpu.ici")


@dataclasses.dataclass
class IciEngineConfig:
    devices: Optional[list] = None  # default: all jax.devices()
    num_groups: int = 1 << 12  # sharded-table groups (divisible by n_dev)
    ways: int = 8
    num_slots: int = 1 << 14  # replica-table slots (num_slots/replica_ways groups)
    replica_ways: int = 4  # replica-table associativity (parallel/ici.py)
    batch_size: int = 1024
    batch_limit: int = 1000
    batch_wait_s: float = 500e-6
    max_flush_items: int = 8192
    max_waves: int = 32  # per-flush wave cap; overflow carries over
    sync_wait_s: float = 0.1  # GLOBAL sync cadence (reference 100ms)
    # Observability knobs — same semantics as EngineConfig (GUBER_HOTKEYS_K
    # / GUBER_STAGE_METADATA / GUBER_EXEMPLARS; docs/monitoring.md).
    hotkeys_k: int = 128
    stage_metadata: bool = False
    exemplars: bool = True
    # Table-census knobs — same semantics as EngineConfig
    # (GUBER_TABLE_CENSUS_TTL / _THRESHOLDS / _HEATMAP; the census runs
    # over BOTH tiers: sharded table + replica 0 of the GLOBAL tier).
    census_ttl_s: float = 5.0
    census_thresholds: tuple = (1, 4, 16)
    census_heatmap_width: int = 64
    # Admission-accounting cadence — same semantics as EngineConfig
    # (GUBER_ADMISSION_TTL; the scan covers BOTH tiers).
    admission_ttl_s: float = 5.0
    # Table layout for BOTH the sharded and replica tiers (the
    # ops/kernels.py LAYOUTS registry): fused serves, wide is the
    # reference tests build.
    layout: str = "fused"
    # Per-tick sync work cap (groups). The tick merges only groups whose
    # content diverges across replicas or that hold pending deltas, up
    # to this many per tick (overflow carries; diag backlog gauge).
    # Bounds tick device time by ACTIVE traffic instead of table size,
    # keeping the 100ms cadence at 10M+ key geometries. None = merge
    # the full table every tick.
    max_sync_groups: "int | None" = 65536
    # Fingerprint-collision backstop (GUBER_ICI_FULL_TICK_EVERY): the
    # capped tick selects groups by comparing two salted
    # non-cryptographic fingerprints across replicas — a collision makes
    # a diverged group look converged and strands it forever. Forcing a
    # full-table tick every N capped ticks bounds that window to
    # N * sync_wait_s. 0 = off; ignored when max_sync_groups is None
    # (the uncapped tick already merges the full table).
    full_tick_every: int = 64
    # Continuous-batching pipeline depth (GUBER_PIPELINE_DEPTH): max
    # flushes dispatched-but-unsynced at once; 1 = serial pump. Same
    # semantics as EngineConfig.pipeline_depth — both ici tiers'
    # (sharded + replica) waves launch in the dispatch stage and sync
    # in the completion stage.
    pipeline_depth: int = 2
    # Paged-table knobs (GUBER_TABLE_PAGE_*) — same semantics as
    # EngineConfig: page_groups > 0 swaps the sharded tier to the paged
    # addressing layer (parallel/mesh.py), with the page map replicated
    # across the mesh, the physical frames owner-sharded, and one
    # resident-frame pool + host-DRAM cold tier per shard. The replica
    # tier stays flat (it is already capacity-bounded per device).
    page_groups: int = 0
    page_budget: int = 0
    page_demote_interval_s: float = 2.0
    page_free_target: int = 1
    # Key-string dictionary (GUBER_KEEP_KEY_STRINGS semantics): needed
    # for routable Loader/handover snapshots — same default as
    # EngineConfig. record_columnar_keys stays off (the columnar edge
    # on this engine predates the dictionary; object-path and inject
    # traffic keep it complete enough for handover).
    keep_key_strings: bool = True
    record_columnar_keys: bool = False
    # Columnar width buckets stay off: every narrowed width would
    # cold-compile a second SPMD program per shape on the mesh.
    fast_buckets: bool = False


class IciEngine(MeshEngine):
    # GLOBAL-flagged requests are routed to the replica tier inside the
    # engine; V1Service must not strip the flag (see the GLOBAL bulk
    # submission in server._get_rate_limits)
    routes_global_internally = True

    def __init__(self, config: IciEngineConfig = IciEngineConfig(), now_fn=_clock.now_ms):
        cfg = config
        devices = cfg.devices or jax.devices()
        if cfg.num_groups % len(devices):
            raise ValueError("num_groups must divide by device count")
        if cfg.num_slots % (cfg.replica_ways * len(devices)):
            raise ValueError(
                "num_slots must divide by replica_ways * device count"
            )
        # Sync-cadence counters exist BEFORE the core constructor: the
        # metrics bridge may scrape a half-built engine during warmup.
        # Overflow observability (VERDICT r3 item 5): keys degraded to
        # per-replica counting right now, and a running total of overflow
        # entries dropped under full-group pressure.
        self._sync_errors = 0
        self.overflow_keys = 0
        self.overflow_drops = 0
        self.sync_backlog = 0
        # What the ticks' deltas did to buckets their owners held
        # (running totals): the hits other replicas took, and those of
        # them the owner's bucket could no longer take.
        self.merged_hits = 0
        self.over_admitted_hits = 0
        # Backstop bookkeeping (gubernator_ici_full_ticks): host-side
        # capped-tick counter and a running total of forced full ticks.
        self.full_ticks = 0
        self._capped_ticks = 0

        super().__init__(cfg, now_fn, topology=IciMeshTopology(devices))

        # A tick in three parts, exposed at 0 from the start (sync_now).
        self._tick_stage = tuple(
            self.metrics.ici_tick_stage_duration.declare(s)
            for s in ("lock_wait", "launch", "read")
        )
        self._stop_sync = threading.Event()
        self._sync_thread = threading.Thread(
            target=self._sync_loop, daemon=True, name="ici-sync"
        )
        self._sync_thread.start()

    # -- compat views over the core's topology state --------------------------

    @property
    def n_dev(self) -> int:
        return self.topo.n_dev

    @property
    def mesh(self):
        return self.topo.mesh

    @property
    def num_rgroups(self) -> int:
        return self._rtier.num_rgroups

    @property
    def ici_state(self):
        return self._rtier.state

    @ici_state.setter
    def ici_state(self, state) -> None:
        self._rtier.state = state

    # -- public additions over the core ---------------------------------------

    def sync_now(self) -> None:
        """Run one GLOBAL sync tick immediately (tests/benchmarks; the
        background sync thread's tick body)."""
        now = self.now_fn()
        t0 = time.perf_counter()
        rt = self._rtier
        # The tick in three parts, each a histogram child and, in a
        # capture, a span on this thread's line: the wait for the engine
        # lock and the collective guard, the launch under them, the
        # read (clock marks here, as under the lock in _execute_waves).
        t_ask = time.perf_counter_ns()
        live = tracing.open_live("tick.lock_wait", {}, otel=False)
        try:
            with self._lock, self.topo.dispatch_guard():
                t_in = time.perf_counter_ns()
                live = tracing.next_live(live, "tick.launch", otel=False)
                # The tick is warmed in _warmup and must stay
                # compile-free on the 100ms cadence — a cold tick stalls
                # GLOBAL convergence, so it counts against the
                # cold-compile invariant too.
                with _telemetry.serving_scope(self.metrics), tracing.span(
                    "ici.sync_tick", level="DEBUG"
                ) as tick_span:
                    sync = rt.sync
                    if rt.sync_full is not None:
                        self._capped_ticks += 1
                        if self._capped_ticks >= self.cfg.full_tick_every:
                            # Collision backstop: merge the FULL table
                            # this tick, healing any group a fingerprint
                            # collision hid from the capped selector.
                            self._capped_ticks = 0
                            self.full_ticks += 1
                            sync = rt.sync_full
                    rt.state, diag = sync(rt.state, now)
                    t_launched = time.perf_counter_ns()
                    live = tracing.next_live(live, "tick.read", otel=False)
                    with _transfer.account(
                        self.metrics, "d2h", "census"
                    ) as tx:
                        d = np.asarray(diag)
                        tx.add(d)
                    t_read = time.perf_counter_ns()
                    live = tracing.next_live(live)
                # kept/dropped cover groups merged THIS tick; under a
                # capped backlog, retained keys in unmerged groups
                # surface when their group's turn comes. The backlog
                # gauge (identical on every device; diag rows replicate
                # it) is the overload signal.
                self.overflow_keys = int(d[:, 0].sum())
                self.overflow_drops += int(d[:, 1].sum())
                self.sync_backlog = int(d[:, 2].max())
                self.merged_hits += int(d[:, 5].sum())
                self.over_admitted_hits += int(d[:, 6].sum())
        finally:
            tracing.next_live(live)
        dur = time.perf_counter() - t0
        groups = int(d[:, 3].max())
        width = int(d[:, 4].max())
        em = self.metrics
        em.ici_tick_duration.observe(dur)
        for child, ns in zip(self._tick_stage, (
            t_in - t_ask, t_launched - t_in, t_read - t_launched
        )):
            child.observe(ns * 1e-9)
        em.ici_tick_groups.observe(groups)
        em.ici_tick_width.observe(width)
        em.recorder.record(
            path="ici-sync", layout=self.cfg.layout, groups=groups,
            width=width,
            backlog=self.sync_backlog, overflow_keys=self.overflow_keys,
            dur_us=int(dur * 1e6),
            trace_id=tracing.trace_id_of(tick_span),
        )

    def inject_globals(self, globals_) -> None:  # guberlint: allow-engine-core-drift -- replica-tier semantics: authoritative pushes land on EVERY replica, not the sharded table
        """Apply an authoritative UpdatePeerGlobals push to every replica
        (the cross-pod/DCN leg landing on an ici-mode daemon)."""
        from gubernator_tpu.models.bucket import FIXED_SHIFT
        from gubernator_tpu.ops.inject import InjectBatch

        if not globals_:
            return
        now = self.now_fn()
        cfg = self.cfg
        rt = self._rtier
        asm = _WaveAssembler(InjectBatch.zeros, cfg.batch_size)
        hi_a, lo_a, slot_a = key_hash128_batch(
            [g.key for g in globals_], rt.num_rgroups
        )
        for i, g in enumerate(globals_):
            slot = int(slot_a[i])
            ib, w, lane = asm.place(slot)
            leaky = int(g.algorithm) == 1
            ib.key_hi[lane] = int(hi_a[i])
            ib.key_lo[lane] = int(lo_a[i])
            ib.group[lane] = slot
            ib.algo[lane] = int(g.algorithm)
            ib.status[lane] = int(g.status.status)
            ib.limit[lane] = g.status.limit
            ib.duration[lane] = g.duration
            ib.remaining[lane] = (
                g.status.remaining << FIXED_SHIFT if leaky else g.status.remaining
            )
            ib.stamp[lane] = now
            ib.expire_at[lane] = g.status.reset_time
            ib.burst[lane] = g.status.limit if leaky else 0
            ib.active[lane] = True
            asm.commit(w, slot)
        with self._lock, self.topo.dispatch_guard():
            state = rt.state
            with _transfer.account(self.metrics, "h2d", "inject") as tx:
                for ib in asm.waves:
                    state = rt.inject(state, ib, now)
                    tx.add(ib)
            rt.state = state

    def close(self) -> None:  # guberlint: allow-engine-core-drift -- adds the sync-thread teardown around the core's close; all drain logic stays super()'s
        self._stop_sync.set()
        super().close()
        self._sync_thread.join(timeout=5)

    # -- sync loop -------------------------------------------------------------

    def _sync_loop(self) -> None:
        while not self._stop_sync.wait(self.cfg.sync_wait_s):
            wd = self.watchdog
            if wd is not None:
                wd.beat("ici-sync", period_s=self.cfg.sync_wait_s)
            try:
                self.sync_now()
                self._sync_errors = 0
            except Exception:
                # Surface persistent failures: without sync, replicas stop
                # converging and GLOBAL limits silently stop aggregating.
                self._sync_errors += 1
                if self._sync_errors in (1, 10) or self._sync_errors % 100 == 0:
                    log.exception(
                        "GLOBAL ICI sync tick failed (%d consecutive)",
                        self._sync_errors,
                    )
