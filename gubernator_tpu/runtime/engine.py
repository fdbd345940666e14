"""Unified mesh engine: micro-batch assembly + the TPU-resident counter
table, ONE core parameterized by mesh shape (runtime/topology.py).

This is the TPU-native replacement for the reference's entire execution
engine (reference workers.go:54-626): instead of sharding the key space
across single-threaded goroutine workers with channel hops, requests
accumulate into fixed-shape device batches and one launch of the packed
decide program (ops/kernels.py decide_packed) updates the HBM slot table in
place. At mesh shape ``(1,)`` that table lives on one chip (DeviceEngine);
at ``(chips,)`` it shards across the mesh under shard_map with psum-merged
outputs, plus a per-device GLOBAL replica tier (IciEngine,
runtime/ici_engine.py) — same core, same assembler, different strategy.

The micro-batching policy transfers directly from the reference's peer
batching (reference peer_client.go:284-337; config.go:126-128): flush at
`batch_limit` items or after `batch_wait` (default 500µs), whichever
first; NO_BATCHING requests flush immediately.

Duplicate handling (SURVEY.md §7 hard part (a)): the reference serializes
same-key requests through one worker, so in-batch duplicates see each
other's effects in request order, and an over-limit rejection does NOT
consume. The assembler reproduces this with *waves*: within one flush,
requests whose slot-group is already taken by an earlier request go to the
next wave; waves apply in order, a launch each or a run stacked into one
(ops/layout.py packed_waves). Groups keep a wave's scatters disjoint.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import queue
import threading
import time
from concurrent.futures import Future
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from gubernator_tpu.utils import lockorder
from gubernator_tpu.utils import raceguard
from gubernator_tpu.metrics import (
    ENGINE_STAGES,
    FLUSH_STAGES,
    STORE_SEQUENCES,
    STORE_WAVE_PROGRAMS,
    engine_flushes_over_max_waves,
    engine_histograms,
    engine_store_counters,
    engine_store_wave_crossings,
    engine_wave_programs,
    engine_wave_transfers,
)
from gubernator_tpu.api.keys import group_of, key_hash128, key_hash128_batch
from gubernator_tpu.api.types import (
    Behavior,
    ERR_ENGINE_DRAINING,
    MAX_BATCH_SIZE,
    RateLimitReq,
    RateLimitResp,
    validate_request,
)
from gubernator_tpu.ops.encode import EncodeError, encode_one, encode_rows
from gubernator_tpu.ops.layout import (
    OPERAND_ROWS,
    OUT_EVICTED_HI,
    OUT_EVICTED_LO,
    OUT_FREED,
    OUT_LIMIT,
    OUT_REMAINING,
    OUT_RESET_TIME,
    OUT_STATUS,
    OUT_TOTALS,
    SlotTable,
    WaveOperand,
    operand_waves,
    split_output,
    wide_rows,
)
from gubernator_tpu.ops.kernels import (
    get_admission,
    get_census,
    get_kernels,
    get_paged_kernels,
)
from gubernator_tpu.runtime import telemetry as _telemetry
from gubernator_tpu.runtime.topology import SingleChipTopology
from gubernator_tpu.utils import clock as _clock
from gubernator_tpu.utils import tracing
from gubernator_tpu.utils import transfer as _transfer


class TableCommittedError(RuntimeError):
    """A device/store failure occurred AFTER waves of this flush already
    committed hits to a still-valid table. Callers must NOT silently
    retry through another path (that would re-apply the committed hits);
    surface the failure to the client instead."""


@dataclasses.dataclass
class EngineConfig:
    """Sizing and batching knobs (defaults mirror the reference's
    BehaviorConfig, config.go:126-140, adapted to device batches)."""

    num_groups: int = 1 << 15  # 32k groups x 8 ways = 256k slots
    ways: int = 8
    batch_size: int = 1024  # lanes per device batch (fixed shape)
    batch_limit: int = 1000  # max requests accumulated per flush
    batch_wait_s: float = 500e-6  # 500 µs
    max_flush_items: int = 8192  # hard cap pulled off the queue per flush
    # Bound per-flush latency: a flush full of same-key duplicates would
    # otherwise serialize into thousands of waves; overflow items carry
    # over to the next flush in arrival order.
    max_waves: int = 32
    keep_key_strings: bool = True  # hash -> string dict (Loader/debug)
    # Record key strings on the STORE-LESS columnar edge too (bulk
    # membership probe + decode of never-seen keys only). Required for
    # ownership handover — an anonymous row cannot be ring-placed at its
    # new owner; daemons running GUBER_HANDOVER=off with no Loader can
    # drop it for the last word of fastpath host time.
    record_columnar_keys: bool = True
    # Graceful-drain budget (GUBER_DRAIN_TIMEOUT): on close() the pump
    # keeps serving whatever is already queued for up to this long;
    # only stragglers past the budget fail, and they fail with the
    # typed retryable status (api.types.ERR_ENGINE_DRAINING) so edges
    # and clients can re-dispatch instead of reporting a loss.
    drain_timeout_s: float = 5.0
    # Continuous-batching pipeline depth (GUBER_PIPELINE_DEPTH): max
    # flushes in flight at once — dispatched to the device (JAX async
    # dispatch; the table threads flush-to-flush as a device-side
    # dependency through the donated buffers) but not yet synced. Depth
    # 1 = the classic serial pump (dispatch, sync, resolve, repeat);
    # depth >= 2 adds a completion thread that syncs tickets in FIFO
    # order while the pump encodes the NEXT flush, so the device never
    # waits on host encode and p99 tracks device time, not dispatch
    # RTT. Decisions are bit-exact across depths (device execution
    # order == dispatch order). A Store pins the effective depth at 1:
    # its read-through probes sync inside the dispatch stage and
    # write-behind must not race the next flush's prefetch.
    pipeline_depth: int = 2
    # Top-K hot-key attribution (GUBER_HOTKEYS_K): tracked entries in
    # the space-saving sketch updated at the flush boundary (keys are
    # already on host there) and served at /debug/hotkeys + as the
    # cardinality-bounded gubernator_hotkey_hits metric. 0 disables the
    # sketch entirely (update sites check once per flush, no per-item
    # cost).
    hotkeys_k: int = 128
    # Per-request stage breakdown in response metadata
    # (GUBER_STAGE_METADATA, default off): when on, each response
    # carries a `stage_breakdown_us` metadata entry with the serving
    # flush's intake->resolve stage times so clients can see where
    # their p99 went. Off = zero per-item bookkeeping.
    stage_metadata: bool = False
    # OpenMetrics exemplars (GUBER_EXEMPLARS): attach the flush span's
    # trace id to the histogram bucket each flush lands in. Only does
    # anything when an OTel SDK records spans AND the scraper negotiates
    # OpenMetrics; off = never attach.
    exemplars: bool = True
    # Background-compile power-of-two batch widths (128..batch_size) so
    # the columnar edge can size the kernel to each call's occupancy.
    fast_buckets: bool = False
    device: Optional[object] = None  # jax device for the table
    # Table layout: "fused" (one tensor of 32-bit words, one gather +
    # one scatter, see ops/fused.py), what a daemon serves from, or
    # "wide" (one int64 column per field), the reference tests build.
    # Both are oracle-exact; Loader snapshots are portable across them
    # (ops/kernels.py LAYOUTS).
    layout: str = "fused"
    # Table observatory (docs/monitoring.md "Table census"): TTL of the
    # cached census snapshot (GUBER_TABLE_CENSUS_TTL) — every scrape
    # surface (occupancy gauges, /debug/table, DebugInfo) reads the
    # cache, so at most ONE census program runs per interval and a
    # slow/concurrent scrape can never stall the pump.
    census_ttl_s: float = 5.0
    # Cold-set idleness thresholds (GUBER_TABLE_CENSUS_THRESHOLDS): a
    # used slot is "cold at kx" when its idle time exceeds k x its own
    # duration; each threshold reports count + reclaimable bytes.
    census_thresholds: tuple = (1, 4, 16)
    # Occupancy heatmap width (GUBER_TABLE_CENSUS_HEATMAP): the group
    # axis aggregates into this many contiguous regions — the future
    # paged-table "page" axis (ROADMAP item 1).
    census_heatmap_width: int = 64
    # Admission observatory (docs/monitoring.md "Admission"): TTL of
    # the cached admitted-vs-limit accounting scan (GUBER_ADMISSION_TTL)
    # — every scrape surface (/debug/admission, the SLI gauges, the
    # auditor's admission pass) reads the cache, so at most ONE
    # admission program runs per interval.
    admission_ttl_s: float = 5.0
    # ---- paged table (GUBER_TABLE_PAGE_*, docs/architecture.md
    # "Paged table") ----
    # Groups per page (GUBER_TABLE_PAGE_GROUPS): 0 keeps the classic
    # flat table; > 0 carves the table into fixed-size pages behind a
    # device-resident indirection map (ops/paged.py) with a host-DRAM
    # cold tier for demoted pages (runtime/pager.py). The keyspace
    # (num_groups) stays logical; HBM holds only page_budget pages.
    page_groups: int = 0
    # Resident-page budget (GUBER_TABLE_PAGE_BUDGET): physical page
    # frames in HBM. Required > 0 when page_groups > 0. HBM table bytes
    # = page_budget x page_groups x ways x bytes_per_slot.
    page_budget: int = 0
    # Background demoter cadence (GUBER_TABLE_PAGE_DEMOTE_INTERVAL):
    # seconds between demoter passes; 0 disables the thread (pages then
    # demote only on free-frame pressure in the serving path).
    page_demote_interval_s: float = 2.0
    # Free-frame target (GUBER_TABLE_PAGE_FREE_TARGET): the demoter
    # keeps at least this many frames free so promotions on the serving
    # path rarely pay a demand demote (a device sync under the lock).
    page_free_target: int = 1


class EngineMetrics:
    """Counters + device-tier distributions the observability layer
    exports (scalar names map to the reference's Prometheus catalog,
    docs/prometheus.md; the histogram families, flight recorder, and
    cold-compile counter are this port's device-tier additions —
    docs/monitoring.md). Wired into a daemon's Metrics registry by
    metrics.wire_engine_telemetry()."""

    def __init__(self):
        from gubernator_tpu.runtime.telemetry import (
            FlightRecorder,
            install_compile_listener,
        )

        self.lock = lockorder.make_lock("engine.metrics")
        self.cache_hits = 0
        self.cache_misses = 0
        self.unexpired_evictions = 0
        self.over_limit = 0
        self.batches = 0
        self.waves = 0
        self.requests = 0
        self.batch_duration_sum = 0.0
        self.cold_compiles = 0
        # Device-tier histograms (families defined once in metrics.py so
        # the exposition catalog and this class cannot drift).
        hists = engine_histograms()
        for attr, h in hists.items():
            setattr(self, attr, h)
        self._histograms = tuple(hists.values())
        # Arrays that crossed the host-device boundary for serving
        # waves: operands uploaded, outputs read; one of each a wave.
        self.wave_transfers = engine_wave_transfers()
        self._wave_h2d = self.wave_transfers.labels("h2d")
        self._wave_d2h = self.wave_transfers.labels("d2h")
        # What a Store costs (docs/persistence.md): the device programs
        # of its per-wave sequence, counted beside the waves, and what
        # the engine asked of the Store itself.
        self.wave_programs = engine_wave_programs()
        self._wave_program = {
            p: self.wave_programs.labels(p) for p in STORE_WAVE_PROGRAMS
        }
        self.store_wave_crossings = engine_store_wave_crossings()
        self._store_crossing = tuple(
            self.store_wave_crossings.labels(d) for d in ("h2d", "d2h")
        )
        # Columnar flushes that ran more than max_waves waves (one key
        # more often than that in one call): further launches of the
        # same flush, not a refusal.
        self.flushes_over_max_waves = engine_flushes_over_max_waves()
        stores = engine_store_counters()
        for attr, c in stores.items():
            setattr(self, attr, c)
        self.store_counters = tuple(stores.values())
        self._store_hit = self.store_gets.labels("hit")
        self._store_miss = self.store_gets.labels("miss")
        self._store_flush = {
            q: self.store_flushes.labels(q) for q in STORE_SEQUENCES
        }
        # Pre-resolved stage children (labels() lookups are per-flush
        # hot-path cost).
        self._stage = {
            s: self.stage_duration.labels(s) for s in ENGINE_STAGES
        }
        for s in FLUSH_STAGES:
            self.stage_duration.declare(s)
        # Engine occupancy: the union of the intervals in which at least
        # one flush is between asking for the engine lock and the end of
        # its readback (busy_enter / busy_exit).
        self._busy_lock = lockorder.make_lock("engine.metrics.busy")
        self._busy_n = 0
        self._busy_t0 = 0.0
        self._busy_s = 0.0
        self.recorder = FlightRecorder()
        install_compile_listener()

    def histograms(self) -> tuple:
        return self._histograms

    @property
    def wave_h2d(self) -> int:
        return int(self._wave_h2d.get())

    @property
    def wave_d2h(self) -> int:
        return int(self._wave_d2h.get())

    def observe_stage(self, stage: str, dur: float) -> None:
        self._stage[stage].observe(dur)

    def busy_enter(self) -> None:
        with self._busy_lock:
            if self._busy_n == 0:
                self._busy_t0 = time.perf_counter()
            self._busy_n += 1

    def busy_exit(self) -> None:
        with self._busy_lock:
            self._busy_n -= 1
            if self._busy_n == 0:
                self._busy_s += time.perf_counter() - self._busy_t0

    def busy_clock(self) -> tuple:
        """(busy seconds so far, the clock they are read on), taken at
        one instant: an interval still open counts up to now."""
        with self._busy_lock:
            now = time.perf_counter()
            open_s = now - self._busy_t0 if self._busy_n else 0.0
            return self._busy_s + open_s, now

    def observe_transfer(self, direction: str, purpose: str,
                         n_bytes: int, dur: float) -> None:
        """One accounted host<->device transfer (utils/transfer.py):
        per-(direction, purpose) bytes + latency distributions — the
        promote/demote bandwidth ledger (docs/monitoring.md "Device
        resources")."""
        self.transfer_duration.labels(direction, purpose).observe(dur)
        self.transfer_bytes.labels(direction, purpose).observe(n_bytes)

    def transfer_snapshot(self) -> dict:
        """JSON ledger view: per-(direction, purpose) transfer counts,
        total bytes, and latency quantiles — /debug/device and the
        bench `device` blob read this."""
        out = {}
        for key, s in self.transfer_bytes.label_summaries(qs=()).items():
            out["/".join(key)] = {
                "count": s["count"],
                "bytes": int(s["sum"]),  # guberlint: allow-host-sync -- histogram summary dict, host-only data
            }
        for key, s in self.transfer_duration.label_summaries(
            qs=(0.5, 0.99)
        ).items():
            ent = out.setdefault(
                "/".join(key), {"count": s["count"], "bytes": 0}
            )
            ent["seconds"] = s["sum"]
            ent["p50_s"] = s["p50"]
            ent["p99_s"] = s["p99"]
            secs = ent.get("seconds") or 0.0
            ent["bytes_per_s"] = (
                ent["bytes"] / secs if secs > 0 else 0.0
            )
        return out

    def observe_store_gets(self, hits: int, misses: int) -> None:
        """Store.get calls of one read-through site, by result."""
        if hits:
            self._store_hit.inc(hits)
        if misses:
            self._store_miss.inc(misses)

    def note_cold_compile(self) -> None:
        with self.lock:
            self.cold_compiles += 1

    def observe(self, hits, misses, evic, over, waves, n, dur):
        with self.lock:
            self.cache_hits += hits
            self.cache_misses += misses
            self.unexpired_evictions += evic
            self.over_limit += over
            self.batches += 1
            self.waves += waves
            self.requests += n
            self.batch_duration_sum += dur

    def observe_flush(self, path: str, n: int, waves: int, dur: float,
                      dev: float, trace_id: str = "",
                      collective: bool = False, transfers=(0, 0),
                      launches: int = 0, programs=None,
                      crossings=None, calls: int = 1,
                      sequence: Optional[str] = None) -> None:
        """One flush's distribution samples (per FLUSH, not per
        request). A non-empty trace_id attaches an OpenMetrics exemplar
        to the latency buckets this flush lands in, so a p99 spike in
        Grafana clicks through to the exact trace. `collective` (mesh
        topologies) additionally lands the device time in the
        collective-tick histogram: on a sharded decide the psum merge
        rendezvouses every shard, so this distribution is the
        shard-skew amplifier the SLO layer watches. `transfers` =
        (operands uploaded, outputs read) for the flush's waves and
        `launches` the decide programs it launched (a run of equally
        wide waves is one operand, one launch, one output), counted
        beside the waves themselves so a scrape sees all or none, as
        are `programs`, the launches of the Store's sequence by
        STORE_WAVE_PROGRAMS name, `crossings`, the [uploaded, read]
        arrays that sequence moved under the engine lock, and
        `sequence`, how the flush's waves ran it (STORE_SEQUENCES; all
        three None without a Store). `calls` is the calls the flush served:
        the members of a columnar flush (check_columns' group commit),
        the distinct calls a pump flush coalesced."""
        self.flush_duration.labels(path).observe(dur, trace_id)
        self.device_sync.labels(path).observe(dev, trace_id)
        self.batch_width.labels(path).observe(n)
        self.flush_waves.observe(waves)
        self.flush_launches.observe(launches)
        self.flush_calls.observe(calls)
        self._wave_h2d.inc(transfers[0])
        self._wave_d2h.inc(transfers[1])
        if programs is not None:
            for name, n in programs.items():
                self._wave_program[name].inc(n)
            for child, n in zip(self._store_crossing, crossings):
                child.inc(n)
            self._store_flush[sequence].inc()
        if collective:
            self.collective_tick.observe(dev)


class FlushStages:
    """The tracing.stage() sink of one flush. A stage that closes only
    leaves its interval here; publish(), after the flush's read, hands
    them to the engine's stage histogram in one go and fills `us`,
    which the flight recorder keeps with the flush's record."""

    __slots__ = (
        "em", "ids", "us", "_rows", "h2d", "d2h", "launches", "programs",
        "crossings", "sequence", "calls", "in_host_stage",
    )

    def __init__(self, em: EngineMetrics, flush: int, call: int):
        self.em = em
        self.ids = {"flush": flush, "call": call}
        # the calls this flush serves (ids names the first of them)
        self.calls = 1
        # a columnar flush between "taken" and "launched", counted by
        # the group commit's gate (MeshEngine._left_host_stage)
        self.in_host_stage = False
        # wave operands uploaded / wave outputs read / decide programs
        # launched by this flush (EngineMetrics.observe_flush counts
        # them beside its waves)
        self.h2d = 0
        self.d2h = 0
        self.launches = 0
        # with a Store: the launches of its sequence by
        # STORE_WAVE_PROGRAMS name (_execute_waves)
        self.programs: Optional[Dict[str, int]] = None
        # the arrays that sequence moved across the host-device
        # boundary under the engine lock, [uploaded, read]
        self.crossings: Optional[List[int]] = None
        # and how the flush's waves ran it (STORE_SEQUENCES)
        self.sequence: Optional[str] = None
        # every key from the start: the record shares this dict, and a
        # /debug/engine dump may walk it while publish() fills it in
        self.us: Dict[str, int] = dict.fromkeys(FLUSH_STAGES, 0)
        self._rows: list = []

    def add(self, label: str, t0_ns: int, t1_ns: int) -> None:
        self._rows.append((label, t1_ns - t0_ns))

    def publish(self) -> None:
        rows, self._rows = self._rows, []
        us = self.us
        for label, wall_ns in rows:
            us[label] += wall_ns // 1000
        self.em.stage_duration.observe_many(
            [((label,), wall_ns * 1e-9) for label, wall_ns in rows]
        )


class _Slot:
    """Lock-free result slot for bulk submissions: Future.set_result costs
    ~12µs in lock/notify overhead per item; bulk callers only need the
    final list, so members use plain assignment and ONE real Future
    resolves when the whole entry is processed.

    `span` (the caller's request span, captured once per bulk) and
    `t_enq` (enqueue stamp for GUBER_STAGE_METADATA) are observability
    side-channels — both stay None on the knob-off path. `call` is the
    caller's call sequence number (0: none), so the flush that serves
    the member can name a call in its record. `deadline_ms`
    (absolute epoch ms, GUBER_OVERLOAD only) lets the pump drop the
    member at pickup when the caller already gave up."""

    __slots__ = ("value", "_done", "span", "t_enq", "deadline_ms", "call")

    def __init__(self):
        self.value = None
        self._done = False
        self.span = None
        self.t_enq = None
        self.deadline_ms = None
        self.call = 0

    def set_result(self, v) -> None:
        self.value = v
        self._done = True

    def done(self) -> bool:
        return self._done


class _FlushTicket:
    """One dispatched-but-unsynced flush traveling the dispatch ->
    completion pipeline: the device outputs (un-materialized JAX arrays),
    the host bookkeeping needed to demux them, and the timing marks the
    completion stage turns into histogram samples. Built by an engine's
    _dispatch, consumed exactly once by its _complete (FIFO)."""

    __slots__ = (
        "items",        # [(req, future-like)] — the flush's intake
        "placements",   # per-item routing (engine-specific)
        "outs",         # per-launch (output, waves): device arrays
        "r_outs",       # ici replica-tier (output vector, 1) per wave
        "store_waves",  # store path: the flush's _StoreWaves (None without)
        "served",       # items answered by this flush (excludes carry)
        "carry_n",      # items deferred to the next flush (wave cap)
        "waves",        # wave count
        "widths",       # per-wave device batch widths
        "t0",           # flush assembly start (perf_counter)
        "t_dev",        # device dispatch start
        "t_disp_end",   # dispatch stage end (set by EngineBase._process)
        "host_mark",    # cumulative pump host-busy time at dispatch end
        "seq",          # monotonic flush-ticket sequence (join key)
        "span",         # flush OTel span (dispatch->completion lifecycle)
        "otel_ctx",     # dispatch-time trace context for _complete
        "trace_id",     # sampled trace id hex ('' when unsampled/off)
        "stages",       # FlushStages sink (dispatch -> completion)
    )

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw.get(k))


def _read_waves(outs, fs: FlushStages, with_store: bool = False):
    """THE completion-stage flush-boundary readback, shared by every
    path: ONE blocking read a launch (pipelined engines run it off the
    pump thread, so the device never waits on host encode), split per
    wave and sliced on the host. `outs` is _execute_waves' list of
    (output, waves): one wave's vector, or the (depth, L) array of a
    stacked run whose first `waves` rows are its waves'. Returns ([rows
    (R, B) per wave, indexed by OUT_*], [hits, misses,
    unexpired_evictions, over_limit] summed over the waves). With a
    Store the flush reads through _StoreWaves.read, which adds the
    waves' packed rows to this."""
    rows = []
    totals = np.zeros(OUT_TOTALS, np.int64)
    for o, n in outs:
        o = np.asarray(o)  # guberlint: allow-host-sync -- completion-stage flush-boundary readback
        fs.d2h += 1
        for vec in (o,) if o.ndim == 1 else o[:n]:
            r, t = split_output(vec, with_store)
            rows.append(r)
            totals += t
    return rows, totals.tolist()


class _StoreWaves:
    """What a flush with a Store carries out of the engine lock, and its
    turn at the Store (docs/persistence.md "What a Store costs").

    Under the lock a launch (a wave, or a run of waves that ran
    stacked) starts the host copies of its output and of its packed
    rows (K.gather_rows) and reads only the probe's answer: `runs`
    holds a launch's (first wave, waves, packed rows still on the
    device), `pre[w]` the events wave w's read-through made (an
    inject's displacements and inserts; none where the run was
    stacked). read(), in the flush's `flush.readback` stage after the
    release, reads both arrays of every launch and builds `events` in
    the order the waves ran. A later wave that needs an earlier one's
    rows under the lock (a key displaced between its own waves,
    _wave_readthrough's freshness rule 1) reads them then, through
    host_rows.

    The hand-over: `_lock` is the engine's hand-over lock. take() is
    called under the engine lock, as the last thing before its release
    (or before the flush's first Store.get under the lock), and
    release() after the flush's write-behind, so the Store is handed
    the flushes in the order they held the engine lock and a Store.get
    under the lock sees every earlier flush's hand-over. Whoever holds
    it needs no engine lock to finish, so the two cannot deadlock.
    release() may be called again: every way out of a flush calls it."""

    __slots__ = (
        "lane_reqs", "runs", "rows", "pre", "events", "nbytes", "_lock",
        "_em", "_held",
    )

    def __init__(self, lane_reqs, lock, em: EngineMetrics):
        self.lane_reqs = lane_reqs
        self.runs: List[Tuple[int, int, object]] = []
        # wave w's rows as the wide struct, once its launch's are read
        self.rows: List[Optional[SlotTable]] = []
        self.pre: List[list] = []
        self.events: List[Tuple[str, Tuple[int, int]]] = []  # ('d'|'i', key)
        self.nbytes = 0  # of the packed rows read so far
        self._lock = lock
        self._em = em
        self._held = False

    def take(self) -> None:
        if self._held:
            return
        if not self._lock.acquire(blocking=False):
            # the flush before this one is still reading or writing
            # behind (gubernator_store_handover_waits)
            self._em.store_handover_waits.inc()
            self._lock.acquire()
        self._held = True

    def release(self) -> None:
        if self._held:
            self._held = False
            self._lock.release()

    def add(self, first: int, n: int, rows) -> None:
        """A launch's packed rows, still on the device: (NCOLS, B) of
        wave `first`, or (depth, NCOLS, B) of the `n` waves from it
        that ran stacked."""
        self.runs.append((first, n, rows))
        self.rows += [None] * n

    def host_rows(self, w: int) -> Tuple[SlotTable, bool]:
        """(wave w's gathered rows as the wide struct on the host,
        whether this call was the one that read its launch's)."""
        r = self.rows[w]
        if r is not None:
            return r, False
        first, n, dev = next(
            run for run in self.runs if run[0] <= w < run[0] + run[1]
        )
        packed = np.asarray(dev)  # guberlint: allow-host-sync -- store path: a launch's packed rows, one read; in flush.readback, or under the lock only for a key displaced between its own waves
        self.nbytes += packed.nbytes
        if packed.ndim == 2:
            self.rows[first] = wide_rows(packed)
        else:
            self.rows[first:first + n] = [wide_rows(p) for p in packed[:n]]
        return self.rows[w], True

    def read(self, outs, fs: FlushStages):
        """_read_waves for a flush with a Store, after the release: the
        output and the packed rows of every launch (their copies were
        started under the lock), then `events` per wave as the sequence
        made them: the inject's displacements and inserts, the decide's
        evictions, the keys served (_drop_displaced_strings acts on a
        key's LAST event). A launch that ran stacked is held to what
        its probe promised (gubernator_engine_store_stacked_surprises).
        The table has committed by now: a failed read raises
        TableCommittedError, so that nobody retries the flush through
        another path, and gives up the flush's turn at the Store."""
        try:
            out_rows: list = []
            totals = [0] * OUT_TOTALS
            for out, (_first, n, _dev) in zip(outs, self.runs):
                o_rows, tot = _read_waves([out], fs, True)
                out_rows += o_rows
                totals = [a + b for a, b in zip(totals, tot)]
                # a stacked run: no miss (tot[1]), and rows
                # OUT_EVICTED_HI, OUT_EVICTED_LO, OUT_FREED all zero
                if n > 1 and (tot[1] or any(
                    r[OUT_EVICTED_HI:OUT_FREED + 1].any() for r in o_rows
                )):
                    self._em.store_stacked_surprises.inc()
            events = self.events
            for w, o_rows in enumerate(out_rows):
                self.host_rows(w)
                events += self.pre[w]
                ehi = o_rows[OUT_EVICTED_HI]
                elo = o_rows[OUT_EVICTED_LO]
                gone = (ehi != 0) | (elo != 0)
                events += [
                    ("d", k)
                    for k in zip(ehi[gone].tolist(), elo[gone].tolist())
                ]
                for entry in self.lane_reqs[w].values():
                    events.append(("i", (entry[1], entry[2])))
        except BaseException as e:
            self.release()
            if isinstance(e, Exception):
                raise TableCommittedError(str(e)) from e
            raise
        return out_rows, totals


class _Joined:
    """One columnar call in the group commit's waiting batch
    (MeshEngine.check_columns): what it asked and, once the batch's
    leader has served it, its answer or the exception to raise in its
    own thread. `latch` is a lock used as a one-shot signal: taken
    here, released by whoever wakes the call, so the caller sleeps
    outside the interpreter on its second acquire."""

    __slots__ = ("cols", "now", "fs", "latch", "leads", "out", "exc")

    def __init__(self, cols, now: int, fs: FlushStages):
        self.cols = cols
        self.now = now
        self.fs = fs
        self.latch = threading.Lock()
        self.latch.acquire()
        self.leads = False
        self.out = None
        self.exc = None


class _FlushGate:
    """The group commit's state: `active` columnar flushes between
    "taken" and "launched" (their host stage; a promoted leader counts
    from its promotion), and the calls that wait for the next turn with
    their item count. Every field is read and written under `lock`, a
    leaf (nothing is acquired inside it)."""

    __slots__ = ("lock", "active", "waiting", "items")

    def __init__(self):
        self.lock = lockorder.make_lock("engine.coalesce")
        self.active = 0
        self.waiting: List[_Joined] = []
        self.items = 0


class _WaveAssembler:
    """First-fit placement of requests into scatter-disjoint waves: a
    request goes to the first wave where its slot-group is unused and a
    lane is free. Same key => same group => strictly increasing wave
    index, which preserves per-key request order."""

    def __init__(self, make_batch, batch_size: int):
        self._make = make_batch
        self._B = batch_size
        self.waves: List[object] = []
        self._groups: List[set] = []
        self._fill: List[int] = []

    def place(self, grp: int, max_waves: Optional[int] = None):
        """Returns (wave_batch, wave_index, lane), or None if placement
        would exceed max_waves (caller carries the item to the next
        flush)."""
        w = 0
        while True:
            if w == len(self.waves):
                if max_waves is not None and w >= max_waves:
                    return None
                self.waves.append(self._make(self._B))
                self._groups.append(set())
                self._fill.append(0)
            if grp not in self._groups[w] and self._fill[w] < self._B:
                return self.waves[w], w, self._fill[w]
            w += 1

    def commit(self, w: int, grp: int) -> None:
        self._groups[w].add(grp)
        self._fill[w] += 1

    def fill(self, w: int) -> int:
        """Occupied lanes in wave w (the wave's device-width floor)."""
        return self._fill[w]


class EngineBase:
    """Shared request intake for device engines: the queue, the bulk
    submission path, and the pump thread's accumulate-and-flush loop
    (the reference's micro-batch policy, peer_client.go:284-337).

    Subclasses provide cfg (batch_wait_s/batch_limit/max_flush_items/
    max_waves/pipeline_depth), now_fn, metrics, and the two pipeline
    stages: _dispatch(items) -> (carry, ticket) — assemble + encode on
    host and launch the kernels WITHOUT a host sync — and
    _complete(ticket) — materialize device results, feed telemetry, and
    resolve futures. carry is the list of (req, future) pairs the flush
    could not place (wave cap); the pump re-presents them first on the
    next flush. _process glues the stages: serially at depth 1 (today's
    pump, bit-exact), through the bounded in-flight ring + completion
    thread at depth >= 2 (continuous batching: host encode of flush N+1
    overlaps device execution of flush N)."""

    @raceguard.init_path
    def _init_base(self, thread_name: str) -> None:
        # guberlint: allow-unbounded-queue -- bounded at intake by the overload governor (GUBER_INTAKE_LIMIT sheds past-budget puts in check_async/check_bulk); knob-off keeps the historical unbounded bit-exact contract
        self._queue: "queue.SimpleQueue" = queue.SimpleQueue()
        self._running = True
        # Intake admission governor (service/overload.py IntakeGovernor,
        # duck-typed like the watchdog seam): the daemon injects it when
        # GUBER_OVERLOAD is on; None means admit everything (bit-exact).
        self.overload = None
        self._draining = False
        # Flush-ticket sequence (pump-thread only; the drain pass runs
        # on the same thread): the /debug/engine <-> trace join key.
        self._ticket_seq = itertools.count(1)
        self._stage_md = bool(getattr(self.cfg, "stage_metadata", False))
        hk = getattr(self.metrics, "hotkeys", None)
        if hk is not None:
            hk.configure(int(getattr(self.cfg, "hotkeys_k", 128) or 0))
            if hasattr(self, "key_string"):
                hk.set_resolver(self.key_string)
        # Bulk entries whose members may span flushes (wave-cap carry);
        # resolved by whichever thread completes their last member.
        self._bulks: List[_Bulk] = []
        self._bulks_lock = lockorder.make_lock("engine.bulks")
        # Table-census cache (docs/monitoring.md "Table census"): every
        # scrape surface reads this snapshot, so at most one census
        # program runs per TTL interval and scrapes never hold the
        # serving lock through device work (guberlint GL009).
        self._census_lock = lockorder.make_lock("engine.census")
        self._census_cache: Optional[dict] = None
        self._census_ts = 0.0
        self._census_prev = None  # (t_mono, misses, evictions, live)
        # Admission-accounting cache (docs/monitoring.md "Admission"):
        # same single-scan-per-TTL contract as the census, separate
        # cadence knob (GUBER_ADMISSION_TTL).
        self._admission_lock = lockorder.make_lock("engine.admission")
        self._admission_cache: Optional[dict] = None
        self._admission_ts = 0.0
        # Shard-skew attribution (multi-device topologies only):
        # cumulative per-shard decided-lane counts, host numpy, updated
        # by the pump at wave granularity (docs/monitoring.md "SLOs &
        # burn rates"). The future PodSliceTopology placement work will
        # be judged against this skew signal (ROADMAP item 1).
        self._shard_lock = lockorder.make_lock("engine.shards")
        self._shard_decisions = (
            np.zeros(self.topo.n_dev, dtype=np.int64)
            if self.topo.n_dev > 1
            else None
        )
        # The replica tier's twin: GLOBAL lanes answered, by the home
        # device the host assigned them (same lock, same topologies).
        self._replica_decisions = (
            np.zeros(self.topo.n_dev, dtype=np.int64)
            if self.topo.n_dev > 1
            else None
        )
        # Cumulative pump time spent in _dispatch (host encode + launch);
        # pump-thread-only writer, read by the completion stage for the
        # host/device overlap ratio.
        self._host_busy = 0.0
        # Pump-thread only: when the oldest entry of the batch being
        # flushed was enqueued (_pump sets it, _dispatch shows the wait
        # in a capture).
        self._queue_since = None
        # Liveness (runtime/watchdog.py): the daemon injects its
        # Watchdog after construction; until then beats are no-ops.
        # The pump and completion threads are SERVING loops — their
        # stall burns the availability SLO, not just a lamp.
        self.watchdog = None
        depth = max(int(getattr(self.cfg, "pipeline_depth", 1) or 1), 1)
        self._pipe_depth = depth
        self._pipe_q: Optional["queue.SimpleQueue"] = None
        self._pipe_thread: Optional[threading.Thread] = None
        if depth > 1:
            # In-flight ring: the semaphore's permits ARE the ring slots
            # (backpressure: the pump blocks acquiring a slot before it
            # launches more device work); the SimpleQueue carries tickets
            # to the completion thread in FIFO dispatch order.
            self._pipe_sem = threading.Semaphore(depth)
            # guberlint: allow-unbounded-queue -- bounded by construction: the pipeline semaphore's `depth` permits cap how many tickets can be in the queue at once
            self._pipe_q = queue.SimpleQueue()
            self._pipe_lock = lockorder.make_lock("engine.pipeline")
            self._inflight = 0
            self._pipe_thread = threading.Thread(
                target=self._completion_loop,
                name=thread_name + "-complete", daemon=True,
            )
            self._pipe_thread.start()
        self._thread = threading.Thread(
            target=self._pump, name=thread_name, daemon=True
        )
        self._thread.start()

    # -- two-stage pipeline --------------------------------------------------

    def _pipeline_active(self) -> bool:
        """Pipelined completion applies only while serving (the drain
        pass completes inline for deterministic straggler accounting)
        and only store-less: the Store path's read-through probes sync
        inside the dispatch stage anyway, and its write-behind must not
        race the NEXT flush's prefetch."""
        return (
            self._pipe_q is not None
            and not self._draining
            and getattr(self, "store", None) is None
        )

    def _process(self, items: List[Tuple[RateLimitReq, object]]) -> list:
        """One flush through both stages. Serial mode (depth 1, store
        attached, or draining): dispatch then complete inline — exactly
        the classic pump. Pipelined mode: dispatch, then hand the ticket
        to the completion thread and return immediately so the pump can
        assemble the next flush while the device executes this one."""
        pipelined = self._pipeline_active()
        if pipelined:
            # Backpressure BEFORE launching more device work: a full
            # ring means the device is the bottleneck — adding waves
            # would only grow the unsynced frontier.
            self._pipe_sem.acquire()
        t_host0 = time.perf_counter()
        try:
            carry, ticket = self._dispatch(items)
        except Exception:
            if pipelined:
                self._pipe_sem.release()
            raise
        end = time.perf_counter()
        self._host_busy += end - t_host0
        if ticket is None:
            if pipelined:
                self._pipe_sem.release()
            return carry
        ticket.t_disp_end = end
        ticket.host_mark = self._host_busy
        if pipelined:
            with self._pipe_lock:
                self._inflight += 1
                depth = self._inflight
            self.metrics.pipeline_inflight.observe(depth)
            self._pipe_q.put(ticket)
        else:
            self.metrics.pipeline_inflight.observe(1)
            self._complete_ticket(ticket)
        return carry

    def _complete_ticket(self, t) -> None:
        """Run the completion stage under the ticket's dispatch-time
        trace context (the completion thread otherwise runs
        context-less — write-behind / resolve errors would land
        trace-orphaned), then end the flush span. The `engine.complete`
        child span gives the completion stage its own timing node with
        thread-crossing parentage under the flush span."""
        err = None
        try:
            # The completion stage is serving-path device work too: its
            # materializations must never compile. PR 6 moved them off
            # the pump thread (whose dispatch-site scope no longer
            # covers them), so mark this thread for the ticket's
            # duration or a completion-side retrace goes uncounted.
            with _telemetry.serving_scope(self.metrics), tracing.attached(
                t.otel_ctx
            ):
                if t.span is not None:
                    with tracing.span(
                        "engine.complete", level="DEBUG", ticket_seq=t.seq
                    ):
                        self._complete(t)
                else:
                    self._complete(t)
        except Exception as e:
            err = e
            raise
        finally:
            tracing.end_span(t.span, error=err)
            t.span = None
            sw = getattr(t, "store_waves", None)
            if sw is not None:
                # a Store flush's turn at the Store ends here at the
                # latest, whatever _complete raised before its own release
                sw.release()

    def _completion_loop(self) -> None:
        """Completion stage: sync each in-flight ticket in FIFO dispatch
        order, resolve its futures, feed the histograms. A failed ticket
        fails ONLY its own futures (earlier tickets already completed;
        later ones dispatched against the recovered table) — the loop
        itself never dies while the engine runs."""
        while True:
            # Bounded get so the idle loop still heartbeats: a blocking
            # get() would look wedged to the watchdog whenever no
            # tickets flow, and a REAL wedge (stuck device sync inside
            # _complete_ticket) would be indistinguishable from idle.
            wd = self.watchdog
            if wd is not None:
                wd.beat("engine-complete", serving=True)
            # In a capture the wait for a ticket is a span of its own:
            # while it is open nothing is in flight.
            live = tracing.open_live("complete.idle", {}, otel=False)
            try:
                t = self._pipe_q.get(timeout=0.5)
            except queue.Empty:
                continue
            finally:
                tracing.next_live(live)
            if t is _STOP:
                return
            try:
                self._complete_ticket(t)
            except Exception as e:
                self._ticket_failed(t, e)
            finally:
                with self._pipe_lock:
                    self._inflight -= 1
                self._pipe_sem.release()
                self._sweep_bulks()

    def _ticket_failed(self, ticket, exc) -> None:
        """An in-flight ticket's results could not be materialized: fail
        that ticket's unresolved futures, then rebuild the table if the
        failed device call consumed (or poisoned) its donated buffers.
        Recovery is idempotent — a healthy table is left alone — so a
        burst of failing tickets rebuilds exactly once."""
        import logging

        err = str(exc)
        # Failure handling runs under the ticket's dispatch-time trace
        # context too: the ERROR-level span (kept at every configured
        # trace level) lands the failure under the flush's trace.
        with tracing.attached(getattr(ticket, "otel_ctx", None)):
            with tracing.span(
                "engine.ticket_failed", level="ERROR", error=err,
                ticket_seq=getattr(ticket, "seq", None) or 0,
            ):
                for _req, fut in ticket.items:
                    if not fut.done():
                        fut.set_result(RateLimitResp(error=err))
                try:
                    self._recover_after_failure()
                except Exception:
                    logging.getLogger(__name__).exception(
                        "table recovery after failed in-flight flush failed"
                    )

    def _observe_overlap(self, ticket) -> None:
        """Host/device overlap sample for one completed flush: host
        dispatch work done for OTHER flushes while this one was in
        flight, as a fraction of its in-flight window. Serial mode pins
        this at 0 — the pump idles while the device runs."""
        window = time.perf_counter() - ticket.t_disp_end
        overlap = self._host_busy - ticket.host_mark
        ratio = min(overlap / window, 1.0) if window > 0 else 0.0
        self.metrics.pipeline_overlap.observe(ratio)

    def _pipeline_quiesce(self) -> None:
        """Wait until every in-flight ticket has completed, and switch
        _process to inline completion (drain mode). Pump-thread only —
        acquiring every ring slot is only ticket-free when no other
        producer can interleave."""
        self._draining = True
        if self._pipe_q is None:
            return
        for _ in range(self._pipe_depth):
            self._pipe_sem.acquire()
        for _ in range(self._pipe_depth):
            self._pipe_sem.release()

    def _sweep_bulks(self) -> None:
        """Resolve bulk futures whose members have all been answered.
        Serial mode sweeps from the pump after each flush; pipelined
        mode sweeps from the completion thread after each ticket."""
        done: List[_Bulk] = []
        with self._bulks_lock:
            still = []
            for b in self._bulks:
                if all(s.done() for s in b.slots):
                    done.append(b)
                else:
                    still.append(b)
            self._bulks[:] = still
        for b in done:
            b.resolve()

    def _resolve_all_bulks(self) -> None:
        """Shutdown tail: resolve every remaining bulk — members never
        served fill in as typed-retryable (see _Bulk.resolve)."""
        with self._bulks_lock:
            rest = list(self._bulks)
            self._bulks[:] = []
        for b in rest:
            b.resolve()

    # -- flush-span lifecycle (docs/monitoring.md "Tracing the pipeline") ----

    def _flush_seq(self) -> int:
        """Next flush sequence: the pump's tickets and the columnar
        flushes of the serving threads draw from one counter, so a
        flush's id names it in /debug/engine and in a capture alike."""
        return next(self._ticket_seq)

    def _start_flush_span(self, flush_items, seq: int, **attributes):
        """Start the per-ticket flush span (ends at completion, possibly
        on another thread) and wire the batch-boundary links: the flush
        span links to each distinct request span it serves, and each
        request span links back to the flush span. Returns None when
        tracing is off — the entire method is then two cheap calls."""
        fspan = tracing.start_span(
            "engine.flush", level="DEBUG",
            pipeline_depth=self._pipe_depth, ticket_seq=seq, **attributes,
        )
        if fspan is None:
            return None
        seen = set()
        for _req, fut in flush_items:
            rs = getattr(fut, "span", None)
            if rs is None or id(rs) in seen:
                continue
            seen.add(id(rs))
            tracing.link(fspan, rs)
            tracing.link(rs, fspan)
        return fspan

    def hotkeys_snapshot(self) -> dict:
        """JSON payload for /debug/hotkeys (service/gateway.py)."""
        hk = getattr(self.metrics, "hotkeys", None)
        if hk is None:
            return {"k": 0, "total_hits": 0, "max_error": 0, "entries": []}
        return hk.snapshot()

    def device_memory(self) -> dict:
        """Per-subsystem HBM attribution + headroom (utils/devicemem.py,
        docs/monitoring.md "Device resources"). Host arithmetic over
        geometry sized at init plus one allocator stats query — never
        dispatches device work, so the scrape-path sync and
        /debug/device can call it freely (GL009)."""
        from gubernator_tpu.utils import devicemem

        subs = dict(getattr(self, "_mem_subsystems", None) or {})
        # snapshot_staging is transient: report the latest staging
        # high-water mark (bytes the last snapshot()/restore() staged),
        # not a phantom always-resident copy.
        subs["snapshot_staging"] = int(
            getattr(self, "_snapshot_staging_bytes", 0)
        )
        subs.setdefault("ici_replicas", 0)
        return devicemem.snapshot(subs, devices=self.devices)

    # -- public intake -------------------------------------------------------

    def check_async(self, req: RateLimitReq) -> "Future[RateLimitResp]":
        """Enqueue one request; resolves after its wave executes."""
        t_in = time.perf_counter()
        fut: Future = Future()
        if not self._running:
            # The pump already exited its drain phase; nothing will ever
            # pull this entry, so fail it typed-retryable immediately
            # instead of letting the future hang.
            fut.set_result(RateLimitResp(error=ERR_ENGINE_DRAINING))
            return fut
        err = validate_request(req)
        if err is not None:
            fut.set_result(RateLimitResp(error=err))
            return fut
        ov = self.overload
        if ov is not None:
            shed, dl = ov.admit(req, self._queue.qsize())
            if shed is not None:
                fut.set_result(shed)
                return fut
            if dl is not None:
                fut.deadline_ms = dl
        if req.created_at is None:
            req.created_at = self.now_fn()
        # Request-span capture for the batch-boundary link (None unless
        # an SDK records a span in this caller's context).
        rs = tracing.current_span()
        if rs is not None:
            fut.span = rs
        t_enq = time.perf_counter()
        self.metrics.observe_stage("intake", t_enq - t_in)
        if self._stage_md:
            fut.t_enq = t_enq
        self._queue.put((req, fut, t_enq))
        return fut

    def check_bulk(
        self, reqs: Sequence[RateLimitReq], call: int = 0
    ) -> "Future[List[RateLimitResp]]":
        """Bulk check: ONE queue entry and ONE Future for N requests
        (amortizes pump wakeups and future overhead; the natural fit for
        the batched GetRateLimits API). Resolves in request order.
        `call` is the caller's call sequence number (tracing.CallRecord),
        carried to the flush record."""
        t_in = time.perf_counter()
        out: Future = Future()
        if not self._running:
            out.set_result(
                [RateLimitResp(error=ERR_ENGINE_DRAINING) for _ in reqs]
            )
            return out
        slots: List[_Slot] = []
        work = []
        now = None
        ov = self.overload
        depth = self._queue.qsize() if ov is not None else 0
        # One request-span capture per BULK (members share the caller's
        # context): the flush that serves them links back to this span.
        rs = tracing.current_span()
        for req in reqs:
            slot = _Slot()
            slot.span = rs
            slot.call = call
            slots.append(slot)
            err = validate_request(req)
            if err is not None:
                slot.set_result(RateLimitResp(error=err))
                continue
            if ov is not None:
                shed, dl = ov.admit(req, depth)
                if shed is not None:
                    slot.set_result(shed)
                    continue
                if dl is not None:
                    slot.deadline_ms = dl
            if req.created_at is None:
                if now is None:
                    now = self.now_fn()
                req.created_at = now
            work.append((req, slot))
        if work:
            b = _Bulk(work, slots, out)
            self.metrics.observe_stage("intake", b.t_enq - t_in)
            if self._stage_md:
                for s in slots:
                    s.t_enq = b.t_enq
            self._queue.put(b)
        else:
            out.set_result([s.value for s in slots])
        return out

    def check_batch(self, reqs: Sequence[RateLimitReq]) -> List[RateLimitResp]:
        """Synchronous batched check (returns in request order)."""
        return self.check_bulk(reqs).result()

    def flush_now(self) -> None:
        """Force the pump to flush without waiting the batch window."""
        self._queue.put(_FLUSH)

    def close(self) -> None:
        """Drain, then stop. The pump keeps serving whatever is already
        queued (the FIFO guarantees everything enqueued before this call
        is seen before _STOP), then runs a bounded drain pass for
        entries that raced the shutdown; only stragglers past
        cfg.drain_timeout_s fail, with the typed retryable status
        (api.types.ERR_ENGINE_DRAINING) so callers can re-dispatch."""
        drain_s = max(float(getattr(self.cfg, "drain_timeout_s", 5.0)), 0.0)
        self._queue.put(_STOP)
        self._thread.join(timeout=5 + drain_s)
        self._running = False  # backstop for a wedged pump
        # The bucket warmer compiles inside XLA C++ frames; if it is
        # still alive when the interpreter finalizes, its GIL touch
        # turns into pthread_exit's forced unwind through C++ catch(...)
        # blocks — glibc aborts with "FATAL: exception not rethrown".
        # _running=False stops it between shapes (a warmer parked until
        # its stacked shapes are wanted is woken to see it); join past
        # the current compile.
        warm = getattr(self, "_warm_thread", None)
        if warm is not None and warm.is_alive():
            self._stack_wanted.set()
            warm.join(timeout=60)
        comp = self._pipe_thread
        if comp is not None and comp.is_alive():
            # The pump sends _STOP at the end of its drain; this second
            # sentinel is a backstop for a wedged pump (extra sentinels
            # are harmless — the loop exits on the first one it sees).
            self._pipe_q.put(_STOP)
            comp.join(timeout=5 + drain_s)

    # -- introspection (shared) ----------------------------------------------

    def debug_snapshot(self) -> dict:
        """Telemetry + flight-recorder snapshot served as JSON by the
        /debug/engine endpoint (service/gateway.py). Host-side state
        plus one occupancy readback; safe at poll cadence."""
        em = self.metrics
        cfg = self.cfg
        with em.lock:
            counters = {
                "requests": em.requests,
                "batches": em.batches,
                "waves": em.waves,
                "cache_hits": em.cache_hits,
                "cache_misses": em.cache_misses,
                "unexpired_evictions": em.unexpired_evictions,
                "over_limit": em.over_limit,
                "cold_compiles": em.cold_compiles,
            }
        snap = {
            "engine": type(self).__name__,
            "layout": getattr(cfg, "layout", ""),
            "batch_size": cfg.batch_size,
            "max_waves": cfg.max_waves,
            "pipeline_depth": self._pipe_depth,
            "inflight": getattr(self, "_inflight", 0),
            "queue_depth": self.queue_depth(),
            "counters": counters,
            "histograms": {h.name: h.summary() for h in em.histograms()},
            "flight_recorder": em.recorder.snapshot(),
        }
        if hasattr(self, "occupancy_stats"):
            snap["occupancy"] = self.occupancy_stats()
        return snap

    # -- table census (docs/monitoring.md "Table census") --------------------

    def table_census(self, max_age_s: Optional[float] = None) -> dict:
        """TTL-cached table census — the table observatory's single
        entry point (occupancy gauges, /debug/table, DebugInfo, and the
        occupancy_stats()/live_count() back-compat views all read it).

        The scan runs OFF the hot path and OUTSIDE the pump-critical
        lock section: the engine lock is held only long enough to
        dispatch the NON-donating census program against the live table
        reference (JAX async dispatch — no host sync under the lock);
        the O(buckets) materialization happens after release, in
        _census_scan. Pass max_age_s=0 to force a fresh scan."""
        ttl = (
            float(getattr(self.cfg, "census_ttl_s", 5.0))
            if max_age_s is None
            else float(max_age_s)
        )
        with self._census_lock:
            if (
                self._census_cache is not None
                and time.monotonic() - self._census_ts < ttl
            ):
                return self._census_cache
            snap = self._census_scan()
            snap["churn"] = self._census_churn(snap)
            self._census_cache = snap
            self._census_ts = time.monotonic()
            return snap

    # -- admission accounting (docs/monitoring.md "Admission") ---------------

    def admission_snapshot(self, max_age_s: Optional[float] = None) -> dict:
        """TTL-cached admitted-vs-limit accounting — the admission
        observatory's single entry point (/debug/admission, the SLI
        gauges, DebugInfo, and the auditor's admission pass all read
        it). Same dispatch discipline as table_census: the engine lock
        is held only long enough to dispatch the NON-donating admission
        program (async — no host sync under the lock); the O(buckets)
        materialization happens after release, in _admission_scan.
        Pass max_age_s=0 to force a fresh scan."""
        ttl = (
            float(getattr(self.cfg, "admission_ttl_s", 5.0))
            if max_age_s is None
            else float(max_age_s)
        )
        with self._admission_lock:
            if (
                self._admission_cache is not None
                and time.monotonic() - self._admission_ts < ttl
            ):
                return self._admission_cache
            snap = self._admission_scan()
            self._admission_cache = snap
            self._admission_ts = time.monotonic()
            return snap

    def cached_census(self) -> Optional[dict]:
        """The census snapshot ONLY if already cached — never scans.
        The SLO sampler reads SLIs at a fixed cadence and must do zero
        device work (GL009/cold_compiles==0 pinned): table_census(ttl)
        dispatches a device program when the cache is stale, which a
        background sampler must never trigger on its own clock. Returns
        None until some scrape/debug hit has populated the cache."""
        with self._census_lock:
            return self._census_cache

    def cached_admission(self) -> Optional[dict]:
        """The admission snapshot ONLY if already cached — never scans.
        Same zero-device-work contract as cached_census()."""
        with self._admission_lock:
            return self._admission_cache

    # -- shard-skew attribution (docs/monitoring.md "SLOs & burn rates") -----

    def _note_shard_decisions(self, waves) -> None:
        """Fold each wave's active lanes onto their owning shard.
        Groups map to shards contiguously (parallel/mesh.py
        _mask_to_local: shard = group // groups_per_shard), so a host
        bincount reproduces the device-side ownership split exactly.
        Pure numpy over already-host wave batches — no device work."""
        n_dev = self.topo.n_dev
        groups = (
            self.K.num_phys_pages * self.K.groups_per_page
            if self._pager is not None
            else self.cfg.num_groups
        )
        groups_per = max(groups // n_dev, 1)
        counts = np.zeros(n_dev, dtype=np.int64)
        for wb in waves:
            act = np.asarray(wb.active)  # guberlint: allow-host-sync -- wave batches carry host-built columns, never device tensors
            grp = np.asarray(wb.group)[act]  # guberlint: allow-host-sync -- wave batches carry host-built columns, never device tensors
            if grp.size:
                counts += np.bincount(
                    np.minimum(grp // groups_per, n_dev - 1),
                    minlength=n_dev,
                )
        with self._shard_lock:
            self._shard_decisions += counts

    def _note_replica_decisions(self, counts) -> None:
        """Add a flush's GLOBAL lanes, counted by home device, to the
        replica tier's totals: that a window's lanes (and a run's
        probes) met every replica is a count, not an inference from the
        round-robin. Host arithmetic on what the assembly already
        holds."""
        with self._shard_lock:
            self._replica_decisions += counts

    def shard_stats(self) -> Optional[dict]:
        """Per-shard skew attribution for the mesh path: decisions (the
        ownership split of served lanes), occupancy (census heatmap
        folded onto shard boundaries — regions and shards are both
        contiguous over groups), page-churn / frame-pool pressure (the
        pager's per-shard rows), and the derived max/mean imbalance
        ratio that feeds the shard-balance SLO. None on single-device
        topologies. Zero device work: reads the cumulative host
        counters and the ALREADY-CACHED census only."""
        n_dev = self.topo.n_dev
        if n_dev <= 1 or self._shard_decisions is None:
            return None
        with self._shard_lock:
            decisions = self._shard_decisions.tolist()
            replica_decisions = self._replica_decisions.tolist()

        def imbalance(vals) -> Optional[float]:
            total = sum(vals)
            if total <= 0:
                return None
            mean = total / float(len(vals))
            return round(max(vals) / mean, 4)

        out: dict = {
            "n_shards": n_dev,
            "decisions": decisions,
            "decision_imbalance": imbalance(decisions),
            "replica_decisions": replica_decisions,
        }
        census = self.cached_census()
        if census is not None:
            tier = census.get("tiers", {}).get(
                self.topo.primary_tier, census
            )
            heat = tier.get("heatmap") or []
            gpr = int(tier.get("heatmap_groups_per_region", 1) or 1)
            groups = int(tier.get("groups", 0) or 0)
            if heat and groups:
                per = max(groups // n_dev, 1)
                occ = [0] * n_dev
                for r, live in enumerate(heat):
                    s = min((r * gpr) // per, n_dev - 1)
                    occ[s] += int(live)
                out["occupancy"] = occ
                out["occupancy_imbalance"] = imbalance(occ)
            pages = census.get("pages")
            if pages and pages.get("shards"):
                out["pages"] = pages["shards"]
                resident = [
                    int(s.get("resident", 0)) for s in pages["shards"]
                ]
                out["resident_imbalance"] = imbalance(resident)
        # Headline gauge: the worst imbalance across dimensions — max/
        # mean == 1.0 is perfectly balanced; the SLO spec alerts on
        # sustained excess.
        dims = [
            v
            for v in (
                out.get("decision_imbalance"),
                out.get("occupancy_imbalance"),
                out.get("resident_imbalance"),
            )
            if v is not None
        ]
        out["imbalance_ratio"] = max(dims) if dims else None
        return out

    @raceguard.holds_lock("engine.census")
    def _census_churn(self, snap: dict) -> dict:
        """Churn ledger: interval deltas of the flush bookkeeping the
        engine already keeps, turned into rates at census cadence.
        `overwrite_recycles` (inserts that reclaimed an expired/freed
        resident slot) is derived by conservation: every insert either
        lands on an empty slot (live grows), evicts an unexpired
        occupant (counted), or recycles a dead resident — the
        remainder. Called with _census_lock held."""
        em = self.metrics
        with em.lock:
            misses, evics = em.cache_misses, em.unexpired_evictions
        t = time.monotonic()
        prev = self._census_prev
        self._census_prev = (t, misses, evics, snap["live"])
        if prev is None:
            return {
                "interval_s": 0.0,
                "insertions": 0,
                "evictions": 0,
                "overwrite_recycles": 0,
                "insert_per_s": 0.0,
                "evict_per_s": 0.0,
                "recycle_per_s": 0.0,
            }
        dt = max(t - prev[0], 1e-9)
        d_ins = max(misses - prev[1], 0)
        d_ev = max(evics - prev[2], 0)
        d_live = snap["live"] - prev[3]
        d_rec = max(d_ins - d_ev - max(d_live, 0), 0)
        return {
            "interval_s": round(dt, 6),
            "insertions": d_ins,
            "evictions": d_ev,
            "overwrite_recycles": d_rec,
            "insert_per_s": round(d_ins / dt, 3),
            "evict_per_s": round(d_ev / dt, 3),
            "recycle_per_s": round(d_rec / dt, 3),
        }

    # -- pump ----------------------------------------------------------------

    def _pump(self) -> None:
        NB = int(Behavior.NO_BATCHING)
        carry: List[Tuple[RateLimitReq, object]] = []
        while self._running:
            wd = self.watchdog
            if wd is not None:
                # Serving heartbeat: a pump stuck behind the pipeline
                # semaphore (wedged completion thread) stops beating
                # here and burns the availability SLO.
                wd.beat("engine-pump", serving=True)
            if not carry:
                try:
                    item = self._queue.get(timeout=0.1)
                except queue.Empty:
                    continue
            else:
                # Wave-capped leftovers from the previous flush go first
                # (preserves per-key arrival order); drain anything queued
                # without waiting.
                try:
                    item = self._queue.get_nowait()
                except queue.Empty:
                    item = _FLUSH
            if item is _STOP:
                self._running = False
                break
            batch: List[Tuple[RateLimitReq, object]] = list(carry)
            carry = []
            # The enqueue of this batch's oldest entry: `flush.queue`
            # in a capture runs from there to the flush's start.
            self._queue_since = None

            def _extend(entry) -> bool:
                """Add a queue entry (single triple or bulk); True if it
                asks for an immediate flush. Queue wait (enqueue ->
                pump pickup) feeds the queue_wait histogram: sustained
                growth means the pump is falling behind intake. With the
                overload governor injected, the same wait drives its
                CoDel controller, and members whose caller deadline
                already expired are refused HERE — before any device
                work — instead of being flushed."""
                qw = self.metrics.queue_wait
                ov = self.overload
                if type(entry) is _Bulk:
                    w = time.perf_counter() - entry.t_enq
                    qw.observe(w)
                    if self._queue_since is None:
                        self._queue_since = entry.t_enq
                    live = entry.work
                    if ov is not None:
                        ov.observe_wait(w)
                        live = []
                        for req, slot in entry.work:
                            dl = slot.deadline_ms
                            if dl is not None and ov.deadline_expired(dl):
                                slot.set_result(ov.refuse_expired(req))
                            else:
                                live.append((req, slot))
                        entry.work = live
                    batch.extend(live)
                    with self._bulks_lock:
                        self._bulks.append(entry)
                    if not live:
                        # Every member expired at pickup: the slots are
                        # all resolved, so the bulk future must resolve
                        # now — no flush will ever sweep it.
                        self._sweep_bulks()
                        return False
                    return any(r.behavior & NB for r, _ in live)
                req, fut, t_enq = entry
                w = time.perf_counter() - t_enq
                qw.observe(w)
                if self._queue_since is None:
                    self._queue_since = t_enq
                if ov is not None:
                    ov.observe_wait(w)
                    dl = getattr(fut, "deadline_ms", None)
                    if dl is not None and ov.deadline_expired(dl):
                        fut.set_result(ov.refuse_expired(req))
                        return False
                batch.append((req, fut))
                return bool(req.behavior & NB)

            flush = item is _FLUSH
            if not flush:
                flush = _extend(item)
            deadline = time.monotonic() + self.cfg.batch_wait_s
            while not flush and len(batch) < self.cfg.max_flush_items:
                remaining = deadline - time.monotonic()
                if len(batch) >= self.cfg.batch_limit or remaining <= 0:
                    break
                try:
                    nxt = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
                if nxt is _STOP:
                    self._running = False
                    break
                if nxt is _FLUSH:
                    break
                if _extend(nxt):
                    break
            if batch:
                try:
                    carry = self._process(batch) or []
                    # Resolve bulks whose members have all been answered.
                    # Pipelined mode leaves this to the completion
                    # thread's per-ticket sweep — slots are not set yet
                    # here, and a redundant pump-side scan of every
                    # pending bulk's slots is pure overhead; wave-capped
                    # bulks wait for their carried items either way.
                    if not self._pipeline_active():
                        self._sweep_bulks()
                except Exception as e:  # never kill the pump
                    for _, fut in batch:
                        if not fut.done():
                            fut.set_result(RateLimitResp(error=str(e)))
                    carry = []
                    self._sweep_bulks()
        # Shutdown: sync every in-flight ticket FIRST (FIFO future
        # order; zero-loss elasticity must cover dispatched-but-unsynced
        # flushes too), then drain whatever is still queued within the
        # drain budget (docs/robustness.md), then fail stragglers with
        # the typed retryable status.
        self._pipeline_quiesce()
        carry = self._drain_tail(carry)
        for _, fut in carry:
            if not fut.done():
                fut.set_result(RateLimitResp(error=ERR_ENGINE_DRAINING))
        self._resolve_all_bulks()
        if self._pipe_q is not None:
            self._pipe_q.put(_STOP)

    def _drain_tail(self, carry):
        """Serve queue entries that raced the shutdown signal. Entries
        enqueued before close() are already handled by the main loop
        (FIFO order puts them ahead of _STOP); this pass covers carried
        wave overflow and producers that slipped in between the _STOP
        being seen and _running going False. Flushes complete INLINE
        here (_pipeline_quiesce flipped drain mode). Returns the pairs
        the drain budget could not serve."""
        deadline = time.monotonic() + max(
            float(getattr(self.cfg, "drain_timeout_s", 5.0)), 0.0
        )
        pending = list(carry)

        def pull(entry) -> None:
            if entry is _STOP or entry is _FLUSH:
                return
            if type(entry) is _Bulk:
                pending.extend(entry.work)
                with self._bulks_lock:
                    self._bulks.append(entry)
            else:
                req, fut, _t = entry
                pending.append((req, fut))

        while time.monotonic() <= deadline:
            # Sweep everything currently queued into `pending`.
            while True:
                try:
                    pull(self._queue.get_nowait())
                except queue.Empty:
                    break
            if not pending:
                # Idle: wait one short beat for producers that raced the
                # intake guard (checked _running before it went False),
                # then exit.
                try:
                    pull(self._queue.get(timeout=0.02))
                except queue.Empty:
                    break
                continue
            batch = pending[: self.cfg.max_flush_items]
            pending = pending[self.cfg.max_flush_items:]
            try:
                extra = self._process(batch) or []
            except Exception as e:  # never die mid-drain
                for _, fut in batch:
                    if not fut.done():
                        fut.set_result(RateLimitResp(error=str(e)))
                extra = []
            # Wave-capped leftovers retry first (per-key arrival order).
            pending = list(extra) + pending
            self._sweep_bulks()
        # Past the budget (or idle): hand back the stragglers — including
        # anything still sitting in the queue — so the caller fails them
        # with the typed retryable status instead of leaving futures
        # hanging.
        while True:
            try:
                pull(self._queue.get_nowait())
            except queue.Empty:
                break
        return pending


def _census_tier_snapshot(
    out, *, now, layout, groups, ways, bytes_per_slot, thresholds,
    heatmap_width,
) -> dict:
    """Materialize one tier's CensusOutput (O(buckets) scalars) into a
    JSON-safe dict. Runs OUTSIDE the engine lock — the program was
    dispatched under it; this is the publish step."""
    a = {
        f: np.asarray(getattr(out, f)).tolist()  # guberlint: allow-host-sync -- census readback: O(buckets) scalars at TTL cadence, outside the serving lock
        for f in out._fields
    }
    slots = groups * ways
    live = a["live"]
    waste = a["waste"]
    full_groups = a["full_groups"]
    return {
        "layout": layout,
        "groups": groups,
        "ways": ways,
        "slots": slots,
        "bytes_per_slot": bytes_per_slot,
        "now_ms": int(now),
        "live": live,
        "occupancy": live / float(slots) if slots else 0.0,
        "full_groups": full_groups,
        "full_group_ratio": full_groups / float(groups) if groups else 0.0,
        "waste": waste,
        "waste_frac": waste / float(slots) if slots else 0.0,
        "age_ms_hist": a["age_hist"],
        "age_ms_sum": a["age_sum"],
        "idle_ms_hist": a["idle_hist"],
        "idle_ms_sum": a["idle_sum"],
        "heatmap": a["heatmap"],
        "cold_heatmap": a["cold_heatmap"],
        "heatmap_groups_per_region": -(-groups // heatmap_width),
        "fill_hist": a["fill_hist"],
        "max_full_run": a["max_full_run"],
        "cold": [
            {
                "multiplier": int(k),
                "slots": c,
                "frac": c / float(slots) if slots else 0.0,
                "reclaimable_bytes": c * bytes_per_slot,
            }
            for k, c in zip(thresholds, a["cold"])
        ],
    }


def _census_combine(tiers: Dict[str, dict], primary: str) -> dict:
    """Top-level census snapshot: tier-summed residency/age/cold
    numbers (what capacity planning wants) plus the primary tier's
    structural fields (heatmap, fill histogram, probe pressure —
    geometry-specific, meaningless summed across different group/way
    shapes). Full per-tier payloads ride under "tiers"."""
    p = tiers[primary]
    live = sum(t["live"] for t in tiers.values())
    slots = sum(t["slots"] for t in tiers.values())
    waste = sum(t["waste"] for t in tiers.values())

    def vsum(field):
        its = [t[field] for t in tiers.values()]
        return [sum(vals) for vals in zip(*its)]

    cold = []
    for i, entry in enumerate(p["cold"]):
        cold.append(
            {
                "multiplier": entry["multiplier"],
                "slots": sum(t["cold"][i]["slots"] for t in tiers.values()),
                "frac": (
                    sum(t["cold"][i]["slots"] for t in tiers.values())
                    / float(slots)
                    if slots
                    else 0.0
                ),
                "reclaimable_bytes": sum(
                    t["cold"][i]["reclaimable_bytes"] for t in tiers.values()
                ),
            }
        )
    return {
        "v": 1,
        "layout": p["layout"],
        "groups": p["groups"],
        "ways": p["ways"],
        "slots": slots,
        "bytes_per_slot": p["bytes_per_slot"],
        "now_ms": p["now_ms"],
        "live": live,
        "occupancy": live / float(slots) if slots else 0.0,
        "full_groups": p["full_groups"],
        "full_group_ratio": p["full_group_ratio"],
        "waste": waste,
        "waste_frac": waste / float(slots) if slots else 0.0,
        "age_ms_hist": vsum("age_ms_hist"),
        "age_ms_sum": sum(t["age_ms_sum"] for t in tiers.values()),
        "idle_ms_hist": vsum("idle_ms_hist"),
        "idle_ms_sum": sum(t["idle_ms_sum"] for t in tiers.values()),
        "heatmap": p["heatmap"],
        "cold_heatmap": p["cold_heatmap"],
        "heatmap_groups_per_region": p["heatmap_groups_per_region"],
        "fill_hist": p["fill_hist"],
        "max_full_run": p["max_full_run"],
        "cold": cold,
        "tiers": tiers,
    }


def _admission_tier_dict(out) -> dict:
    """Materialize one AdmissionOutput (or an oracle dict) into plain
    host ints/lists — the per-tier payload of admission_snapshot."""
    if isinstance(out, dict):
        d = dict(out)
    else:
        d = {
            f: np.asarray(getattr(out, f))  # guberlint: allow-host-sync -- admission readback: O(buckets) scalars at TTL cadence, outside the serving lock
            for f in out._fields
        }
    keys, admitted, limit, excess, excess_keys, max_excess, over, hist = (
        d[f]
        for f in (
            "keys", "admitted_sum", "limit_sum", "excess_sum",
            "excess_keys", "max_excess", "over_limit_keys", "excess_hist",
        )
    )
    return {
        "keys": int(keys),
        "admitted_hits": int(admitted),
        "limit_hits": int(limit),
        "excess_hits": int(excess),
        "excess_keys": int(excess_keys),
        "max_excess": int(max_excess),
        "over_limit_keys": int(over),
        "excess_hist": [int(x) for x in hist],
    }


def _admission_combine(tiers: Dict[str, dict]) -> dict:
    """Top-level admission snapshot: everything is additive across
    tiers (each key lives in exactly one tier) except max_excess, which
    takes the max. The over-admission SLI ratio is derived at the top:
    excess hits per configured limit hit, 0 on an empty table."""
    excess = sum(t["excess_hits"] for t in tiers.values())
    limit = sum(t["limit_hits"] for t in tiers.values())
    snap = {
        "v": 1,
        "keys": sum(t["keys"] for t in tiers.values()),
        "admitted_hits": sum(t["admitted_hits"] for t in tiers.values()),
        "limit_hits": limit,
        "excess_hits": excess,
        "excess_keys": sum(t["excess_keys"] for t in tiers.values()),
        "max_excess": max(t["max_excess"] for t in tiers.values()),
        "over_limit_keys": sum(
            t["over_limit_keys"] for t in tiers.values()
        ),
        "excess_ratio": excess / float(limit) if limit else 0.0,
        "excess_hist": [
            sum(vals)
            for vals in zip(*(t["excess_hist"] for t in tiers.values()))
        ],
        "tiers": tiers,
    }
    return snap


class MeshEngine(EngineBase):
    """Owns the slot table; turns request streams into decisions.

    ONE engine core, parameterized by mesh shape (runtime/topology.py):
    the strategy object binds the kernels (plain jits at mesh shape
    ``(1,)``, shard_map ownership programs at ``(chips,)``), decides
    whether a Pager manages page residency behind them, builds the
    GLOBAL replica tier where a mesh exists, and supplies the
    collective-dispatch guard. Everything else — pump, pipeline ring,
    ticket lifecycle, failure recovery, drain, snapshots, census /
    admission caching, flush telemetry — lives here exactly once.

    Thread model: callers (any thread / asyncio executor) enqueue
    (request, Future) pairs; one pump thread drains the queue, assembles
    waves, runs the kernel, and resolves futures. All device state is
    touched only by the pump thread — the moral equivalent of the
    reference's single-writer worker exclusivity (workers.go:19-25)
    with one writer for the whole table.
    """

    # V1Service/fastpath read this to decide whether GLOBAL traffic can
    # be answered locally; the ICI subclass (replica tier) flips it.
    routes_global_internally = False

    def __init__(
        self,
        config: EngineConfig = EngineConfig(),
        now_fn: Callable[[], int] = _clock.now_ms,
        topology=None,
    ):
        self.cfg = config
        self.now_fn = now_fn
        self.topo = topology if topology is not None else SingleChipTopology()
        self.metrics = EngineMetrics()
        self.store = None  # optional Store plugin (gubernator_tpu.store)
        self._skipped_logged = float("-inf")  # _note_skipped_rows' last line
        self._key_strings: Dict[Tuple[int, int], str] = {}
        self._lock = lockorder.make_lock("engine.table")  # guards table swap (load/restore)
        # guards the host key dictionaries (pump + executor threads)
        self._keys_lock = lockorder.make_lock("engine.keys")
        # A flush's turn at the Store (_StoreWaves): taken under
        # self._lock as the flush leaves it, held over its deferred
        # reads and its write-behind, never the other way round.
        self._handover = lockorder.make_lock("engine.handover")
        # Standby replication dirty-key harvest (parallel/standby.py):
        # key string -> hits dirtied since the last drain, fed by the
        # flush completion paths alongside the hotkey aggregation — no
        # extra device work, no extra table pass. None (the default)
        # keeps both flush paths bit-exact; only the ReplicationManager
        # enables it.
        self._dirty: Optional[Dict[str, int]] = None
        self._dirty_lock = lockorder.make_lock("engine.dirty")

        if config.max_waves < 1:
            raise ValueError("max_waves must be >= 1")

        # Kernel binding + table residency are the topology's call: the
        # paged facade (docs/architecture.md "Paged table") swaps in the
        # paged addressing layer — PHYSICAL table shrunk to the
        # resident-frame budget, Pager tracking residency and the
        # host-DRAM cold tier (one frame pool + cold tier PER SHARD on
        # a mesh) — while flat binds the full-size table directly.
        self.K, self._pager = self.topo.build_kernels(config, self.metrics)
        # Where a wave's operand is uploaded to (None: the default
        # device; the configured device; replicated over the mesh).
        self._operand_sharding = self.topo.operand_sharding(config)
        # Every facade accepts (and the paged/mesh ones ignore) the
        # flat geometry args, so creation is uniform across all four
        # kernel cases.
        self.table = self._place(
            lambda: self.K.create(config.num_groups, config.ways)
        )

        # GLOBAL replica tier (parallel/ici.py) — mesh topologies only.
        self._rtier = self.topo.build_replica(config, self.metrics)
        # Round-robin home cursor for GLOBAL replica placement; host
        # bookkeeping shared by _dispatch and the columnar split.
        self._home_rr = 0

        # Table-observatory program (ops/census.py): one jitted,
        # non-donating scan per (layout, geometry, knobs); warmed in
        # _warmup so the first scrape never compiles. On a mesh the
        # same plain program runs over the sharded array under GSPMD.
        self._census_thresholds = tuple(
            int(k) for k in config.census_thresholds
        )
        self._census = get_census(
            config.layout,
            config.ways,
            heatmap_width=int(config.census_heatmap_width),
            thresholds=self._census_thresholds,
        )
        # Admission-accounting program (ops/admission.py): same
        # non-donating scan contract as the census, warmed alongside it.
        self._admission = get_admission(config.layout, config.ways)

        # HBM attribution (utils/devicemem.py): static geometry sized
        # once; device_memory() folds in allocator stats per call.
        self._mem_subsystems = self._memory_subsystems()
        self._snapshot_staging_bytes = 0

        # The stacked launch's warm (depth, width) shapes, published as
        # _warm_shapes is: _warmup's at batch_size, the ladder's after.
        self._warm_stacks: tuple = ()
        # The same for a Store's stacked sequence (probe, `with_store`
        # decide and row gather of a run): warm_store_path's.
        self._warm_store_stacks: tuple = ()
        # Set when the ladder's stacked shapes are wanted (_warm_buckets).
        self._stack_wanted = threading.Event()
        # Group commit at check_columns' entry.
        self._gate = _FlushGate()
        self._warmup()
        self._init_base(self.topo.thread_name)
        # Columnar-path batch-width buckets compile in the background; the
        # fast path only uses already-warm shapes (a cold compile mid-
        # request would blow through forwarding timeouts — same reason
        # _warmup exists). batch_size itself is warm from _warmup.
        # Published as an immutable tuple swapped atomically by the warmer
        # thread; readers iterate whatever snapshot they observe (mutating
        # a shared set mid-iteration can raise in the reader).
        self._warm_shapes = (config.batch_size,)
        self._warm_thread = None
        if getattr(config, "fast_buckets", False):
            self._warm_thread = threading.Thread(
                target=self._warm_buckets, name="gubernator-warm-buckets",
                daemon=True,
            )
            self._warm_thread.start()
        # Background demoter (paged mode): keeps free-frame headroom by
        # evacuating census-cold pages to the host tier, so serving-path
        # promotions rarely pay a demand demote under the lock.
        self._demote_stop = threading.Event()
        self._demote_thread = None
        if (
            self._pager is not None
            and float(getattr(config, "page_demote_interval_s", 0) or 0) > 0
        ):
            self._demote_thread = threading.Thread(
                target=self._demote_loop, name="gubernator-page-demoter",
                daemon=True,
            )
            self._demote_thread.start()

    @property
    def devices(self) -> list:
        """The jax devices this engine's tables live on: the mesh's on a
        mesh topology, else the configured device (default: the
        process's first)."""
        mesh_devs = getattr(self.topo, "devices", None)
        if mesh_devs:
            return list(mesh_devs)
        dev = getattr(self.cfg, "device", None)
        return [dev if dev is not None else jax.devices()[0]]

    def _place(self, build):
        """Run `build()` — table construction — on the configured device
        and COMMIT the result there. Creation under default_device alone
        leaves the arrays uncommitted, and the first jitted call with
        host operands would move them back to the process default
        device; a committed table pins every later dispatch (and its
        outputs) to its own chip."""
        dev = getattr(self.cfg, "device", None)
        if dev is None:
            return build()
        with jax.default_device(dev):
            return jax.device_put(build(), dev)  # guberlint: allow-unaccounted-transfer -- same-device commit of a freshly built table, no bytes move

    def wait_warm(self, timeout_s: float = 600.0) -> bool:
        """Block until the bucket ladder has finished warming (VERDICT r3
        item 7: the cold-bucket latency cliff must be closable at
        startup, not discovered by the first NO_BATCHING request).

        Returns True when no further shape will ever compile on this
        engine: either the warmer thread finished (all ladder widths
        warm, or it intentionally stopped — store attached / oversized
        table), or fast_buckets is off (batch_size is the only shape and
        _warmup already compiled it). The serving path itself NEVER
        compiles: it narrows only to already-warm widths, so "not yet
        warm" costs a wide-kernel dispatch, never a JIT stall."""
        warm = self._warm_thread
        if warm is None:
            return True
        self._stack_wanted.set()  # the caller wants every shape
        warm.join(timeout=timeout_s)
        return not warm.is_alive()

    def close(self) -> None:
        """Stop the page demoter before the base drain: the demoter
        takes the engine lock and dispatches device work, and the base
        close tears the pump down around that same lock."""
        self._demote_stop.set()
        dem = self._demote_thread
        if dem is not None and dem.is_alive():
            dem.join(timeout=30)
        super().close()

    def _demote_loop(self) -> None:
        """Background demoter (paged mode). Each cycle: read the
        TTL-cached census, and when the resident tier shows cold slots
        (or holds no live rows at all) AND the free-frame list is below
        page_free_target, evacuate LRU pages under the engine lock
        until the headroom target is met. The census gate keeps a fully
        hot working set resident instead of thrashing it through the
        host tier; min_idle_ticks=1 additionally spares pages touched
        by the most recent wave round."""
        interval = max(float(self.cfg.page_demote_interval_s), 0.05)
        while not self._demote_stop.wait(interval):
            wd = self.watchdog
            if wd is not None:
                # period_s widens the stall deadline to cover the
                # configured sleep — a 60s demote cadence is not a wedge.
                wd.beat("page-demoter", period_s=interval)
            try:
                pager = self._pager
                want = int(getattr(self.cfg, "page_free_target", 1) or 0)
                with raceguard.racy_read(
                    "free",
                    reason="lock-free headroom precheck; demote_victims "
                    "re-reads under the table lock",
                ):
                    if want <= 0 or len(pager.free) >= want:
                        continue
                census = self.table_census()
                dev = census.get("tiers", {}).get(
                    self.topo.primary_tier, census
                )
                cold = dev.get("cold") or []
                cold_slots = int(cold[0]["slots"]) if cold else 0  # guberlint: allow-host-sync -- census dict is host data (TTL-cached scrape)
                if int(dev.get("live", 0)) > 0 and cold_slots == 0:
                    continue  # resident set is fully hot: don't thrash
                # Victim policy: fold the census per-region cold-slot
                # heatmap into per-page coldness so the demoter evicts
                # pages whose SLOTS are idle, not merely pages with the
                # oldest touch tick (a single probe re-warms a page's
                # tick; the census still sees its other slots as cold).
                ch = dev.get("cold_heatmap")
                with self._lock, self.topo.dispatch_guard():
                    # The heatmap fold reads page_map, which serving
                    # threads rebind under the table lock — folding
                    # outside it can index a page demoted mid-scan.
                    # Demote cadence only, so holding the lock is cheap.
                    coldness = None
                    if ch:
                        coldness = pager.coldness_from_heatmap(
                            ch, int(dev.get("heatmap_groups_per_region", 1))
                        )
                    self.table = pager.demote_victims(
                        self.table, want_free=want, min_idle_ticks=1,
                        coldness=coldness,
                    )
            except Exception:  # pragma: no cover - defensive
                # The demoter is an optimization: serving-path demand
                # demotes cover for it, so a transient failure (device
                # teardown races at close) must not kill the thread.
                if self._demote_stop.is_set():
                    return
                continue

    # Scratch-table budget for the bucket-warm ladder: beyond this the
    # throwaway compile copy is skipped and only batch_size stays warm —
    # a single-request flush then pays one batch_size-wide dispatch, a
    # LATENCY cost, never a JIT stall (tests/test_engine.py pins this).
    _WARM_TABLE_BUDGET = 512 << 20

    def _warm_buckets(self) -> None:
        """Compile decide at each power-of-two width below batch_size
        against a THROWAWAY table of the same shape — never the live one:
        holding the serving lock through a ~1s compile stalls forwarded
        batches past their timeout, and the resulting client retries
        double-apply hits. The jit cache is keyed on shapes/dtypes, so
        the real table hits the warm entry afterwards."""
        cfg = self.cfg
        # A second table is transient compile fodder; skip bucket warming
        # when that copy would be expensive (huge HBM tables) — the
        # always-warm batch_size shape still serves the fast path. Sized
        # by the LAYOUT's resident bytes/slot.
        # Paged mode subsumes the old whole-table gate: the RESIDENT
        # footprint (physical frames, not the logical keyspace) is what
        # a scratch copy costs, and paging keeps it bounded regardless
        # of num_groups — the budget skip only fires when the resident
        # budget itself is huge.
        if self._pager is not None:
            resident_slots = self.K.num_phys_pages * self.K.page_slots
            approx_bytes = resident_slots * self.K.bytes_per_slot
        else:
            approx_bytes = cfg.num_groups * cfg.ways * self.K.bytes_per_slot
        if approx_bytes > self._WARM_TABLE_BUDGET:
            return
        shapes = self._ladder()

        def warm(B: int, stacked: bool) -> bool:
            """Width B's single-wave launch, or its stacked ones."""
            if not self._running:
                return False
            if self.store is not None:
                # Store-path flushes pin the batch width to batch_size
                # (check_columns skips bucket narrowing), so narrower
                # decide shapes would be dead weight: seconds of compile
                # plus a throwaway table per shape, used by nothing.
                return False
            try:
                # Same device placement as the live table, or the compile
                # lands in a different jit cache entry and the "warm"
                # shape still cold-compiles on first real use.
                scratch = self._place(
                    lambda: self.K.create(cfg.num_groups, cfg.ways)
                )
                if stacked:
                    scratch = self._warm_stacked(scratch, B)
                else:
                    scratch, out = self.K.decide_packed(
                        scratch, self._warm_operand(B, self.now_fn()),
                        cfg.ways, False,
                    )
                    np.asarray(out)  # guberlint: allow-host-sync -- warm-up on a throwaway table, off the serving path: the compile must end before the width is published
                    self._warm_shapes = self._warm_shapes + (B,)
                del scratch
            except Exception:
                # A width that does not compile or does not fit on the
                # device: every call narrower than batch_size then runs
                # at full width, so say which width and why.
                if self._running:
                    import logging

                    logging.getLogger(__name__).exception(
                        "bucket warm-up failed at width %d; widths below "
                        "batch_size=%d stay cold and serve at full width",
                        B, cfg.batch_size,
                    )
                return False
            return True

        for B in shapes:
            if not warm(B, False):
                return
        # The stacked launch (a run of waves in one program) at the
        # ladder's narrowest width: the waves after a flush's first hold
        # its repeated keys alone and narrow to it (batch_size has its
        # own from _warmup; a several-wave columnar call takes the least
        # of the two that holds it). A program costs seconds to compile
        # and about two to load from the cache (measured, PERF.md §6 PR
        # 35), and a daemon that serves one wave a flush never needs
        # these: they are compiled once somebody does, a caller that
        # waits for every shape (wait_warm) or the first run of waves
        # that found none (_upload).
        if shapes:
            self._stack_wanted.wait()
            warm(shapes[0], True)

    def _ladder(self) -> list:
        """The widths below batch_size a launch may be compiled at,
        narrowest first: powers of two from 128 lanes."""
        shapes = []
        b = 128
        while b < self.cfg.batch_size:
            shapes.append(b)
            b <<= 1
        return shapes

    def _join_budget(self) -> int:
        """The items check_columns' waiting batch may hold: the
        narrowest width this engine has a warm launch at (one wave's or
        a stacked run's), and no more than the ladder's first width. A
        merged flush so fits programs that are compiled and warm, at
        the width its smallest member runs at alone. Where nothing
        narrower than batch_size is warm (a Store, a mesh, a table too
        large for the ladder's scratch copy, or not yet) the merged
        flush runs at batch_size as each member would have, and what
        may merge stays what merges everywhere: calls small enough to
        share the narrowest launch. A page of a hundred items is not
        one of them: its waves add up with its peers' (the hot key is
        in each), which wants wider and deeper stacked shapes and a
        read that costs by the byte."""
        narrowest = min(
            self._warm_shapes + tuple(b for _d, b in self._warm_stacks)
        )
        ladder = self._ladder()
        return min(narrowest, ladder[0]) if ladder else narrowest

    def _memory_subsystems(self) -> dict:
        """Static HBM attribution from engine geometry (bytes, computed
        once — device_memory() reads this every scrape without touching
        the device). Estimates, not allocator truth: the gap shows up
        as unattributed_bytes in the snapshot."""
        cfg = self.cfg
        if self._pager is not None:
            # Paged table: HBM holds only the physical frames plus the
            # int32 indirection map; demoted pages live in host DRAM
            # (reported via the census "pages" section, not here —
            # this map attributes DEVICE memory).
            slots = self.K.num_phys_pages * self.K.page_slots
            table_b = slots * self.K.bytes_per_slot
        else:
            slots = cfg.num_groups * cfg.ways
            table_b = slots * self.K.bytes_per_slot
        # Census output: two fixed-width histograms (age/idle), the
        # fill histogram, the heatmap regions, one bucket per coldness
        # threshold, and a handful of scalars — all int64.
        census_b = 8 * (
            2 * 32
            + (cfg.ways + 1)
            + int(cfg.census_heatmap_width)
            + len(self._census_thresholds)
            + 16
        )
        # In-flight decide outputs pinned by the continuous-batching
        # ring: depth x waves x batch lanes x ~8 int64 output columns.
        # A pump flush holds max_waves waves at most. A columnar flush
        # holds its whole call, a wave for every time its hottest key
        # comes (the API's cap is MAX_BATCH_SIZE items), operands and
        # outputs at batch_size lanes with a Store or on a mesh: the
        # most one flush can pin, beside the ring.
        ring_b = cfg.batch_size * 8 * (
            max(int(cfg.pipeline_depth), 1) * cfg.max_waves * 8
            + MAX_BATCH_SIZE * (OPERAND_ROWS + 8)
        )
        # Admission output: one excess histogram plus a handful of int64
        # scalars (ops/admission.py AdmissionOutput).
        admission_b = 8 * (32 + 8)
        subs = {
            "slot_table": table_b,
            "census": census_b,
            "admission": admission_b,
            "pipeline_ring": ring_b,
        }
        if self._pager is not None:
            subs["page_map"] = 4 * self.K.num_logical_pages
        rt = self._rtier
        if rt is not None:
            # GLOBAL replica tier: per-device stacked replica tables +
            # int64 pending deltas (parallel/ici.py IciState) plus the
            # per-device tick scalars.
            subs["ici_replicas"] = (
                self.topo.n_dev * rt.num_slots * (self.K.bytes_per_slot + 8)
                + 8 * self.topo.n_dev
            )
            # Second census/admission program pair over the replica tier.
            subs["census"] += 8 * (
                (rt.replica_ways + 1)
                + int(cfg.census_heatmap_width)
                + len(self._census_thresholds)
                + 16
            )
            subs["admission"] += admission_b
        return subs

    @raceguard.init_path
    def _warmup(self) -> None:
        """Compile the decide AND inject kernels before serving: first XLA
        compilation takes seconds (tens of seconds on TPU), which would
        blow through peer-forwarding / GLOBAL broadcast timeouts (500ms
        default) on the first request."""
        from gubernator_tpu.ops.inject import InjectBatch

        now = self.now_fn()
        # The serving form of every launch: one uploaded operand in, one
        # output vector out (ops/layout.py).
        op = self._warm_operand(self.cfg.batch_size, now)
        with self.topo.dispatch_guard():
            with _transfer.account(self.metrics, "d2h", "warmup") as tx:
                table, out = self.K.decide_packed(
                    self.table, op, self.cfg.ways, self.store is not None
                )
                tx.add(np.asarray(out))
                # The stacked form of the same launch (a run of waves in
                # one program), at every depth the serving path may pick.
                table = self._warm_stacked(table, self.cfg.batch_size)
                table, _, _ = self.K.inject(
                    table, InjectBatch.zeros(self.cfg.batch_size), now,
                    self.cfg.ways,
                )
                tx.add(np.asarray(table.used[:1]))  # guberlint: allow-raw-table-index -- warmup sync probe: any one physical row works, logical identity irrelevant
                # Census compiles here too: the first /metrics or /debug/table
                # scrape must dispatch a warm program, not pay a compile.
                c = self._census(self._census_view(table), now)
                tx.add(np.asarray(c.live))  # guberlint: allow-host-sync -- warmup: compile the census program before serving
                # Admission accounting likewise: the first /debug/admission
                # scrape or auditor pass must never compile.
                a = self._admission(self._census_view(table), now)
                tx.add(np.asarray(a.keys))  # guberlint: allow-host-sync -- warmup: compile the admission program before serving
            if self._pager is not None:
                # Compile the page-migration programs (bind/extract/write/
                # unbind) on a throwaway cycle over frame 0: the first
                # demand promote/demote must not pay a compile under the
                # serving lock. Leaves the table empty and the map unbound.
                PK = self.K
                z = np.int32(0)
                table = PK.bind_page(table, z, z)
                rows = PK.extract_page(table, z)
                with _transfer.account(self.metrics, "d2h", "warmup") as tx:
                    host = {
                        f: np.asarray(getattr(rows, f))  # guberlint: allow-host-sync -- warmup: compile the demote extract path before serving
                        for f in SlotTable._fields
                    }
                    tx.add(host)
                table = PK.write_page(table, z, z, SlotTable(**host))
                table = PK.unbind_page(table, z, z)
            rt = self._rtier
            if rt is not None:
                # Replica-tier programs: decide, the sync tick (both
                # variants), and the stacked census/admission scans —
                # the first GLOBAL request or sync tick must dispatch
                # warm programs.
                with _transfer.account(self.metrics, "d2h", "warmup") as tx:
                    rt.state, r_out = rt.decide(rt.state, op)
                    tx.add(np.asarray(r_out))  # guberlint: allow-host-sync -- warmup: compile the replica decide program before serving
                    rt.state, diag = rt.sync(rt.state, now)
                    tx.add(np.asarray(diag))  # guberlint: allow-host-sync -- warmup: compile the sync tick before the cadence thread runs it
                    if rt.sync_full is not None:
                        rt.state, diag = rt.sync_full(rt.state, now)
                        tx.add(np.asarray(diag))  # guberlint: allow-host-sync -- warmup: compile the full-tick backstop before its first forced tick
                    rc = rt.census(rt.state.table, now)
                    tx.add(np.asarray(rc.live))  # guberlint: allow-host-sync -- warmup: compile the replica census program before serving
                    ra = rt.admission(rt.state.table, now)
                    tx.add(np.asarray(ra.keys))  # guberlint: allow-host-sync -- warmup: compile the replica admission program before serving
                jax.block_until_ready(rt.state.pending)
        self.table = table

    def _census_view(self, table):
        """The tensor the census program scans: the PHYSICAL table in
        paged mode (the host tier is censused separately with the numpy
        oracle in _census_scan), the table itself otherwise."""
        return table.data if self._pager is not None else table

    def _warm_operand(self, lanes: int, now: int, depth=None):
        """An empty wave's operand (or an empty run's, `depth` waves
        deep) on the device, placed as _upload places a serving one (the
        jit cache keys on it), accounted as warm-up."""
        return _transfer.device_put(
            WaveOperand.zeros(lanes, depth).stamp(now).buf,
            self._operand_sharding, metrics=self.metrics, purpose="warmup",
        )

    def _wave_depths(self) -> tuple:
        """The depths a stacked launch is compiled at, least first: a
        run of waves is padded with empty waves to the least that holds
        it. The program loops over the real waves only
        (ops/layout.py packed_waves), so a depth costs its upload and
        its compile, not device time: two are enough, one that holds a
        call of a hundred skewed items (~7 waves) and one that holds
        every run a flush can make."""
        top = self.cfg.max_waves
        if top < 2:
            return ()
        return (8, top) if top > 8 else (top,)

    def _warm_stacked(self, table, lanes: int):
        """Compile the stacked launch at `lanes` for every depth against
        `table` (empty runs: no wave runs, the table comes back as it
        went in) and publish the shapes; returns the table. A paged
        table is served wave by wave (promotion is per wave), so it
        warms none."""
        if self._pager is not None:
            return table
        now = self.now_fn()
        for depth in self._wave_depths():
            table, out = self.K.decide_packed(
                table, self._warm_operand(lanes, now, depth),
                self.cfg.ways, False,
            )
            np.asarray(out)  # guberlint: allow-host-sync -- warm-up: the compile must end before the shape is published
            self._warm_stacks = self._warm_stacks + ((depth, lanes),)
        return table

    def _upload(self, waves, now: int, fs: FlushStages, stack=True) -> list:
        """Stamp `now` into each wave's operand and upload them: THE
        host-to-device crossing of a flush, made BEFORE the flush asks
        for the engine lock, so what runs under the lock launches
        programs whose operands are all on the device. One accounted
        h2d/serve record for the flush.

        Returns the flush's launches in order, [(first wave, waves,
        operand on the device)]. A run of consecutive waves of one
        width is ONE array, stacked to the least warm depth that holds
        it, and one launch; a run longer than the deepest warm depth
        (max_waves: a columnar call whose hot key comes more often) is
        as many launches as it takes, all of this one flush and issued
        under its one hold of the engine lock (_execute_waves). What
        the engine observes decides: a run of
        one wave stays the single-wave operand; a pager keeps the
        per-wave sequence (page promotion is defined per wave), as
        `stack=False` does for the replica tier's waves and for a Store
        flush that already knows it will read through; with a Store a
        lane that can free its row (RESET_REMAINING, the one way `used`
        goes false: ops/decide.py _token_paths) keeps it too, and the
        stacked shapes are the Store sequence's own
        (warm_store_path); a stacked shape that is not warm is not
        used (no compile on the serving path)."""
        if not waves:
            return []
        stack = stack and len(waves) > 1 and self._pager is None
        warm = self._warm_stacks  # immutable snapshot
        if stack and self.store is not None:
            warm = self._warm_store_stacks
            reset = int(Behavior.RESET_REMAINING)
            stack = not any(w.asks(reset) for w in waves)
        runs, bufs = [], []
        w = 0
        while w < len(waves):
            run, n = waves[w], 1
            while stack and w + n < len(waves) and waves[w + n].lanes == run.lanes:
                n += 1
            if n > 1:
                depths = sorted(d for d, b in warm if b == run.lanes)
                if depths:
                    # the least warm depth that holds the run, or as
                    # much of the run as the deepest holds
                    depth = next((d for d in depths if d >= n), depths[-1])
                    n = min(n, depth)
                    run = WaveOperand.stacked(waves[w:w + n], depth)
                else:
                    n = 1
                    self._stack_wanted.set()  # per wave now; warm it for later
            runs.append((w, n))
            bufs.append(run.stamp(now).buf)
            w += n
        fs.h2d += len(bufs)
        ops = _transfer.device_put(
            bufs, self._operand_sharding,
            metrics=self.metrics, purpose="serve",
        )
        return [(w, n, op) for (w, n), op in zip(runs, ops)]

    def warm_store_path(self) -> None:
        """Compile the store-path kernels (the with_store decide variant,
        probe_exists, gather_rows) at serving shapes so the first flush
        doesn't cold-compile under the serving lock: a wave's, and a
        stacked run's at every depth the serving path may pick (with
        the program that hands a run's waves back one by one where its
        probe finds a lane not live). Called by attach_store — at
        daemon init, before traffic, so briefly holding the lock here
        is free."""
        cfg = self.cfg
        now = self.now_fn()
        depths = () if self._pager is not None else self._wave_depths()
        ops = [
            self._warm_operand(cfg.batch_size, now, depth)
            for depth in (None,) + depths
        ]
        with self._lock, self.topo.dispatch_guard(), _transfer.account(
            self.metrics, "d2h", "warmup"
        ) as tx:
            # an empty wave, then an empty run, through the serving
            # sequence, shape for shape
            table = self.table
            for op in ops:
                tx.add(np.asarray(self.K.probe_exists(table, op, cfg.ways)))
                table, out = self.K.decide_packed(table, op, cfg.ways, True)
                self.table = table
                tx.add(np.asarray(self.K.gather_rows(table, out, True)))
                tx.add(np.asarray(out))
                if op.ndim == 3:
                    jax.block_until_ready(operand_waves(op))  # guberlint: allow-host-sync allow-blocking-under-lock -- warm-up at attach time, before traffic: the compile must end before the shape is published
        self._warm_store_stacks = tuple((d, cfg.batch_size) for d in depths)

    # ---- introspection -----------------------------------------------------

    def key_string(self, hi: int, lo: int) -> Optional[str]:
        return self._key_strings.get((hi, lo))

    # ---- standby dirty-key harvest (parallel/standby.py) -------------------

    def enable_dirty_tracking(self) -> None:
        """Turn on the dirty-key registry the standby ReplicationManager
        drains each ship pass. Idempotent. The None default keeps both
        flush paths bit-exact with tracking off (GUBER_STANDBY=0)."""
        with raceguard.racy_read(
            "_dirty", reason="double-checked enable; re-read under the lock"
        ):
            off = self._dirty is None
        if off:
            with self._dirty_lock:
                if self._dirty is None:
                    self._dirty = {}

    def disable_dirty_tracking(self) -> None:
        with self._dirty_lock:
            self._dirty = None

    def drain_dirty_keys(self, max_keys: int = 0) -> Dict[str, int]:
        """Return-and-clear the dirtied {key: hits} accumulated since
        the last drain. With max_keys > 0, at most that many keys drain
        (the rest stay pending for the next pass — the standby loss
        bound keeps counting them). {} when tracking is off."""
        with self._dirty_lock:
            d = self._dirty
            if not d:
                return {}
            if max_keys <= 0 or len(d) <= max_keys:
                out = dict(d)
                d.clear()
                return out
            out = {}
            for k in list(d.keys())[:max_keys]:
                out[k] = d.pop(k)
            return out

    def dirty_hits(self) -> int:
        """Peek (no drain): hits dirtied since the last drain. Feeds the
        live half of the standby loss bound."""
        with self._dirty_lock:
            d = self._dirty
            return sum(d.values()) if d else 0

    def _note_dirty(self, pairs) -> None:
        """Merge [(key, hits)] into the dirty registry (callers already
        checked self._dirty is not None; re-checked under the lock)."""
        with self._dirty_lock:
            d = self._dirty
            if d is None:
                return
            for k, n in pairs:
                d[k] = d.get(k, 0) + n

    def _note_dirty_columnar(self, hi, lo, hits) -> None:
        """Columnar-path harvest: resolve (hi, lo) through the host
        key-string dictionary (anonymous rows are skipped — they are not
        ring-routable, the same contract as handover snapshots)."""
        with self._keys_lock:
            ks = self._key_strings
            resolved = [
                (ks.get((int(h), int(l))), int(n))
                for h, l, n in zip(hi.tolist(), lo.tolist(), hits.tolist())
            ]
        self._note_dirty(
            (k, max(n, 0)) for k, n in resolved if k is not None
        )

    def queue_depth(self) -> int:
        return self._queue.qsize()

    def live_count(self) -> int:
        """Number of occupied slots (gubernator_cache_size analog).
        Thin view over the TTL-cached census: scrapes never run a
        device reduction under the engine lock (guberlint GL009)."""
        return self.table_census()["live"]

    def occupancy_stats(self) -> dict:
        """Back-compat occupancy dict (/debug/engine, DebugInfo): a
        thin view over the TTL-cached census — same shape as the old
        per-scrape device reductions, zero scrape-triggered device
        work (docs/monitoring.md "Table census")."""
        c = self.table_census()
        return {
            "live": c["live"],
            "slots": c["slots"],
            "occupancy": c["occupancy"],
            "full_group_ratio": c["full_group_ratio"],
        }

    def _census_scan(self) -> dict:
        """One census pass (called by table_census with _census_lock
        held): dispatch the non-donating program on the live table
        reference under the engine lock, materialize after release."""
        cfg = self.cfg
        now = self.now_fn()
        host_pages = None
        pages_snap = None
        out_r = None
        with self._lock, self.topo.dispatch_guard():
            out = self._census(self._census_view(self.table), now)
            if self._rtier is not None:
                out_r = self._rtier.census(self._rtier.state.table, now)
            if self._pager is not None:
                # Reference copies under the lock; the numpy census walk
                # happens after release (rows blocks are replace-only).
                host_pages = self._pager.host_tier_copy()
                pages_snap = self._pager.pages_snapshot()
        dev_groups = (
            self.K.num_phys_pages * self.K.groups_per_page
            if self._pager is not None
            else cfg.num_groups
        )
        with _transfer.account(self.metrics, "d2h", "census") as tx:
            tier = _census_tier_snapshot(
                out,
                now=now,
                layout=cfg.layout,
                groups=dev_groups,
                ways=cfg.ways,
                bytes_per_slot=self.K.bytes_per_slot,
                thresholds=self._census_thresholds,
                heatmap_width=int(cfg.census_heatmap_width),
            )
            tx.add(out)
        primary = self.topo.primary_tier
        tiers = {primary: tier}
        if out_r is not None:
            rt = self._rtier
            with _transfer.account(self.metrics, "d2h", "census") as tx:
                tiers["replica"] = _census_tier_snapshot(
                    out_r,
                    now=now,
                    layout=cfg.layout,
                    groups=rt.num_rgroups,
                    ways=rt.replica_ways,
                    bytes_per_slot=self.K.bytes_per_slot,
                    thresholds=self._census_thresholds,
                    heatmap_width=int(cfg.census_heatmap_width),
                )
                tx.add(out_r)
        if self._pager is not None:
            # Host-DRAM tier census (satellite: per-tier counts — the
            # census must not under-report live keys once demotion is
            # on). Pure numpy over the demoted pages' wide rows
            # (ops/census.py census_oracle), no device work.
            tiers["host"] = self._census_host_tier(host_pages, now)
        snap = _census_combine(tiers, primary=primary)
        if pages_snap is not None:
            snap["pages"] = pages_snap
        return snap

    def _census_host_tier(self, host_pages: dict, now: int) -> dict:
        """Census the demoted pages with the numpy oracle; returns the
        same tier dict shape as the device tier so _census_combine sums
        them. Empty host tier -> an all-zero tier (stable schema)."""
        import types

        from gubernator_tpu.ops.census import census_oracle
        from gubernator_tpu.runtime.pager import wide_zeros

        cfg = self.cfg
        ps = self.K.page_slots
        if host_pages:
            lps = sorted(host_pages)
            fields = {
                f: np.concatenate([host_pages[lp][f] for lp in lps])
                for f in SlotTable._fields
            }
        else:
            fields = wide_zeros(ps)  # one empty page: zero counts
        wide = SlotTable(**fields)
        d = census_oracle(
            wide,
            now,
            ways=cfg.ways,
            heatmap_width=int(cfg.census_heatmap_width),
            thresholds=self._census_thresholds,
        )
        groups = (len(host_pages) if host_pages else 0) * (
            ps // cfg.ways
        )
        # groups=0 when the host tier is empty: the zero-page
        # placeholder censused above contributes zero counts and the
        # tier reports 0 slots (fracs guard on slots == 0).
        return _census_tier_snapshot(
            types.SimpleNamespace(_fields=tuple(d.keys()), **d),
            now=now,
            layout=cfg.layout,
            groups=groups,
            ways=cfg.ways,
            bytes_per_slot=self.K.bytes_per_slot,
            thresholds=self._census_thresholds,
            heatmap_width=int(cfg.census_heatmap_width),
        )

    def _admission_scan(self) -> dict:
        """One admission-accounting pass (called by admission_snapshot
        with _admission_lock held): dispatch the non-donating program on
        the live table reference under the engine lock, materialize
        after release. Paged mode scans the PHYSICAL frames on device
        and the demoted host pages with the numpy oracle (same split as
        the census) — a demoted key's window still counts."""
        now = self.now_fn()
        host_pages = None
        out_r = None
        with self._lock, self.topo.dispatch_guard():
            out = self._admission(self._census_view(self.table), now)
            if self._rtier is not None:
                out_r = self._rtier.admission(self._rtier.state.table, now)
            if self._pager is not None:
                host_pages = self._pager.host_tier_copy()
        with _transfer.account(self.metrics, "d2h", "admission") as tx:
            tier = _admission_tier_dict(out)
            tx.add(out)
        tiers = {self.topo.primary_tier: tier}
        if out_r is not None:
            with _transfer.account(self.metrics, "d2h", "admission") as tx:
                tiers["replica"] = _admission_tier_dict(out_r)
                tx.add(out_r)
        if self._pager is not None:
            tiers["host"] = self._admission_host_tier(host_pages, now)
        snap = _admission_combine(tiers)
        snap["now_ms"] = now
        return snap

    def _admission_host_tier(self, host_pages: dict, now: int) -> dict:
        """Admission-account the demoted pages with the numpy oracle;
        returns the same tier dict shape as the device tier so
        _admission_combine sums them. Empty host tier -> all zeros."""
        from gubernator_tpu.ops.admission import admission_oracle
        from gubernator_tpu.runtime.pager import wide_zeros

        ps = self.K.page_slots
        if host_pages:
            lps = sorted(host_pages)
            fields = {
                f: np.concatenate([host_pages[lp][f] for lp in lps])
                for f in SlotTable._fields
            }
        else:
            fields = wide_zeros(ps)  # one empty page: zero counts
        return _admission_tier_dict(
            admission_oracle(SlotTable(**fields), now)
        )

    def hotkeys_snapshot(self) -> dict:
        """/debug/hotkeys payload with the census join: each sketch row
        gains the key's residency bucket — `resident`, `cold` (idle
        past the census cold threshold), `expired` (window elapsed but
        slot still held), or `evicted` — so operators can see whether
        hot keys are fighting cold residents for slots."""
        snap = super().hotkeys_snapshot()
        if self._rtier is not None:
            # GLOBAL keys hash into the replica keyspace (num_rgroups),
            # not the sharded table's groups — the join below would
            # mislabel them, so the replica topology serves the plain
            # sketch snapshot (pre-unification IciEngine behavior).
            return snap
        entries = snap.get("entries") or []
        hashes = [e.get("key_hash") for e in entries]
        if not hashes or any(h is None for h in hashes):
            return snap  # no sketch / legacy rows: nothing to join on
        cfg = self.cfg
        W = cfg.ways
        hi = np.array([h[0] for h in hashes], dtype=np.int64)
        lo = np.array([h[1] for h in hashes], dtype=np.int64)
        grp = np.array(
            [group_of(int(l), cfg.num_groups) for l in lo], dtype=np.int64
        )
        demoted = np.zeros(len(grp), dtype=bool)
        with self._lock:
            if self._pager is not None:
                # Logical -> physical translation through the pager's
                # host mirror; keys on demoted pages gather the
                # out-of-range sentinel (zero rows) and are labeled
                # below instead of probed.
                pgrp = self._pager.phys_groups(grp)
                demoted = pgrp < 0
                grp_dev = np.where(demoted, self.table.num_slots // W, pgrp)
            else:
                grp_dev = grp
            slots = (
                grp_dev[:, None] * np.int64(W)
                + np.arange(W, dtype=np.int64)[None, :]
            ).reshape(-1)
            packed = self.K.gather_rows(self.table, slots)
        # Bounded O(K x ways) readback at debug-poll cadence; the
        # census bucket thresholds mirror table_census semantics.
        n = len(hashes)
        with _transfer.account(self.metrics, "d2h", "census") as tx:
            packed = np.asarray(packed)  # guberlint: allow-host-sync -- hotkeys census join: one read of O(K x ways) packed rows at debug cadence, outside the serving lock
            tx.add(packed)
        rows = wide_rows(packed)
        r_hi, r_lo, r_used, r_lru, r_dur, r_exp = (
            col.reshape(n, W) for col in (
                rows.key_hi, rows.key_lo, rows.used, rows.lru,
                rows.duration, rows.expire_at,
            )
        )
        now = self.now_fn()
        cold_k = self._census_thresholds[
            min(1, len(self._census_thresholds) - 1)
        ]
        for i, e in enumerate(entries):
            if demoted[i]:
                e["census"] = "demoted"  # its page is in the host tier
                continue
            match = r_used[i] & (r_hi[i] == hi[i]) & (r_lo[i] == lo[i])
            if not match.any():
                e["census"] = "evicted"
                continue
            w = int(np.argmax(match))
            if r_exp[i, w] <= now:
                e["census"] = "expired"
            elif now - r_lru[i, w] > cold_k * r_dur[i, w]:
                e["census"] = "cold"
            else:
                e["census"] = "resident"
        snap["cold_multiplier"] = int(cold_k)
        return snap

    # ---- wave assembly + kernel dispatch -----------------------------------

    def _dispatch(
        self, items: List[Tuple[RateLimitReq, object]]
    ) -> Tuple[List[Tuple[RateLimitReq, object]], Optional[_FlushTicket]]:
        """Pipeline stage 1: assemble + encode the flush on host and
        launch its waves (no host sync — JAX async dispatch; the table
        threads flush-to-flush through the donated buffers). Returns
        (carry, ticket); _complete materializes the ticket."""
        t0 = time.perf_counter()
        now = self.now_fn()
        cfg = self.cfg
        B = cfg.batch_size
        seq = self._flush_seq()
        # The flush names the first call it serves (a pump flush may
        # coalesce several) and counts them; entries that name no call
        # (check_async, tests) count as one.
        call_ids = [getattr(f, "call", 0) for _, f in items]
        fs = FlushStages(
            self.metrics, seq, next((c for c in call_ids if c), 0)
        )
        fs.calls = len(set(call_ids))
        # The pump's queue, from the enqueue of the batch's oldest
        # entry to here: a mark at its end, carrying its length.
        since, self._queue_since = self._queue_since, None
        if since is not None and tracing.capturing():
            tracing.rpc_mark("flush.queue", dict(
                fs.ids, wait_us=int((time.perf_counter() - since) * 1e6)
            ))

        # One native batch-hash call for the whole flush (assembler hot
        # loop; gubernator_tpu.native), then one-shot tolist conversions
        # — per-item numpy scalar boxing dominated the assembler loop.
        with tracing.stage("flush.hash", fs, fs.ids):
            hashes = key_hash128_batch(
                [req.hash_key() for req, _ in items], cfg.num_groups
            )
            hi_l, lo_l, grp_l = (
                hashes[0].tolist(), hashes[1].tolist(), hashes[2].tolist()
            )

        # Store read-through happens per WAVE inside the execution loop
        # below, driven by a table-residency probe — the table, not host
        # bookkeeping, defines a cache miss (reference algorithms.go:45-51
        # consults the store on every cache miss). To keep blocking store
        # I/O outside the device lock, keys this process has never seen
        # (absent from _key_strings, which is a superset of table
        # residency) are prefetched HERE; the per-wave probe catches the
        # rare remainder (displaced keys) with a direct fetch.
        prefetched: Dict[Tuple[int, int], object] = {}
        need: list = []  # never-seen keys: the flush knows it reads through
        with tracing.stage("flush.keydict", fs, fs.ids):
            if self.store is not None and cfg.keep_key_strings:
                # the replica tier's GLOBAL buckets are never persisted,
                # so the Store is not asked for them either
                replica = (
                    int(Behavior.GLOBAL) if self._rtier is not None else 0
                )
                with self._keys_lock:
                    seen = set()
                    for i, (req, _) in enumerate(items):
                        if req.behavior & replica:
                            continue
                        k = (hi_l[i], lo_l[i])
                        if k not in self._key_strings and k not in seen:
                            seen.add(k)
                            need.append((req, k))
                for req, k in need:
                    snap = self._store_get(req)
                    if snap is not None:
                        prefetched[k] = snap
                self.metrics.observe_store_gets(
                    len(prefetched), len(need) - len(prefetched)
                )

            if cfg.keep_key_strings:
                self._maybe_prune_key_strings()

        with tracing.stage("flush.waves", fs, fs.ids):
            asm = _WaveAssembler(WaveOperand.zeros, B)
            placements: List[Optional[tuple]] = []
            wave_rows: List[list] = []  # per-wave (req, hi, lo, grp) for bulk fill
            wave_lanes: List[list] = []
            GREG = int(Behavior.DURATION_IS_GREGORIAN)
            GLOBAL = int(Behavior.GLOBAL)
            keep = cfg.keep_key_strings
            rt = self._rtier
            # GLOBAL replica routing (replica topologies): keys re-hash into
            # the replica keyspace, waves assemble per (home, slot) so the
            # round-robin home device rides the wave batch, and placements
            # carry an "r" tag so _complete demuxes from the replica outputs.
            r_asm = _WaveAssembler(WaveOperand.zeros, B) if rt is not None else None

            r_homes = [0] * self.topo.n_dev  # replica lanes placed, by home
            carry: List[Tuple[RateLimitReq, object]] = []
            new_strings: Dict[Tuple[int, int], str] = {}
            for i, (req, fut) in enumerate(items):
                hi, lo = hi_l[i], lo_l[i]
                if keep:
                    new_strings[(hi, lo)] = req.hash_key()
                if rt is not None and (req.behavior & GLOBAL):
                    slot = group_of(lo, rt.num_rgroups)
                    home = self._home_rr % self.topo.n_dev
                    placed = r_asm.place((home, slot), cfg.max_waves)
                    if placed is None:
                        carry.append((req, fut))
                        placements.append("carry")
                        continue
                    self._home_rr += 1
                    wb, w, lane = placed
                    try:
                        encode_one(
                            wb.batch, lane, req, now, rt.num_rgroups,
                            key=(hi, lo),
                        )
                    except EncodeError as e:
                        fut.set_result(RateLimitResp(error=str(e)))
                        placements.append(None)
                        continue
                    wb.home[lane] = home
                    r_asm.commit(w, (home, slot))
                    r_homes[home] += 1
                    placements.append(("r", w, lane, hi, lo))
                    continue
                grp = grp_l[i]
                placed = asm.place(grp, cfg.max_waves)
                if placed is None:
                    # Wave cap reached for this group: defer to the next flush
                    # (the pump re-presents carried items first, preserving
                    # per-key arrival order).
                    carry.append((req, fut))
                    placements.append("carry")
                    continue
                wb, w, lane = placed
                if req.behavior & GREG:
                    # calendar resolution stays per-item (rare path)
                    try:
                        encode_one(
                            wb.batch, lane, req, now, cfg.num_groups,
                            key=(hi, lo),
                        )
                    except EncodeError as e:
                        fut.set_result(RateLimitResp(error=str(e)))
                        placements.append(None)
                        continue
                else:
                    while len(wave_rows) < len(asm.waves):
                        wave_rows.append([])
                        wave_lanes.append([])
                    wave_rows[w].append((req, hi, lo, grp))
                    wave_lanes[w].append(lane)
                asm.commit(w, grp)
                placements.append(("s", w, lane, hi, lo))

            if new_strings:
                with self._keys_lock:
                    self._key_strings.update(new_strings)

            for w, rows in enumerate(wave_rows):
                if rows:
                    encode_rows(asm.waves[w].batch, wave_lanes[w], rows, now)
            waves = asm.waves

            # Bucket each wave's device width to its occupancy (the kernel's
            # cost is per-LANE: a NO_BATCHING single-request flush must not
            # pay a batch_size-wide kernel). Lane indices are arrival ranks,
            # so every occupied lane survives the narrowing; only ALREADY-
            # WARM shapes are used — same policy as the columnar path. With
            # a store, flushes stay batch_size-wide (warm_store_path pins
            # that width for probe/inject/gather).
            if self.store is None:
                warm = self._warm_shapes  # immutable snapshot
                for w in range(len(waves)):
                    fill, Bn = asm.fill(w), B
                    for s in warm:
                        if s >= fill and s < Bn:
                            Bn = s
                    if Bn < B:
                        waves[w] = waves[w].narrowed(Bn)

            # Execute waves sequentially against the (donated) table. With a
            # Store attached, each wave runs the reference's exact per-request
            # sequence at wave granularity (algorithms.go:45-51):
            #   probe (cache lookup) -> Store.Get for misses -> insert -> decide
            # and then gathers its touched rows from the intermediate table so
            # write-behind persists the value the caller observed even if a
            # later wave displaces the slot (OnChange runs within the request,
            # algorithms.go:149-153).
            wave_lane_req: List[Dict[int, tuple]] = [dict() for _ in waves]
            if self.store is not None:
                for i, place in enumerate(placements):
                    if isinstance(place, tuple) and place[0] == "s":
                        wave_lane_req[place[1]][place[2]] = (
                            items[i][0], place[3], place[4],
                        )
            # Per-ticket flush span: starts here, rides the ticket across
            # the pipeline boundary, ends when _complete finishes (the
            # completion thread re-attaches its context — see
            # _complete_ticket). Request spans link to it and back.
            r_waves = r_asm.waves if r_asm is not None else []
            if r_waves:
                self._note_replica_decisions(r_homes)
            n_waves = len(waves) + len(r_waves)
            ops = self._upload(waves, now, fs, stack=not need)
            r_ops = self._upload(r_waves, now, fs, stack=False)
        fspan = self._start_flush_span(
            items, seq, path="object", layout=cfg.layout,
            items=len(items), waves=n_waves,
            batch_width=len(items) - len(carry),
        )
        widths = [w.lanes for w in waves]
        widths += [B] * len(r_waves)  # replica waves stay full-width
        # Retrace attribution (runtime/telemetry.py): stamp this
        # thread's shape signature so a compile observed during the
        # flush names the widths that retraced, not just the program.
        _telemetry.set_shape_hint(f"{cfg.layout}:object:{widths}")
        t_dev = time.perf_counter()
        try:
            with _telemetry.serving_scope(self.metrics), tracing.use_span_ctx(
                fspan
            ):
                outs, r_outs, sw = self._execute_waves(
                    waves, ops, wave_lane_req, now, prefetched, fs,
                    r_ops=r_ops,
                )
        except Exception as e:
            tracing.end_span(fspan, error=e)
            raise
        return carry, _FlushTicket(
            items=items, placements=placements, outs=outs,
            r_outs=r_outs, store_waves=sw,
            served=len(items) - len(carry), carry_n=len(carry),
            waves=n_waves,
            widths=widths,
            t0=t0, t_dev=t_dev, seq=seq, span=fspan,
            otel_ctx=tracing.context_of(fspan),
            trace_id=tracing.trace_id_of(fspan),
            stages=fs,
        )

    def _complete(self, t: _FlushTicket) -> None:
        """Pipeline stage 2: materialize the ticket's device results
        (one host sync per wave), feed telemetry, run write-behind, and
        resolve the futures — in FIFO dispatch order when pipelined."""
        cfg = self.cfg
        fs = t.stages
        sw = t.store_waves
        t_c0 = time.perf_counter()
        # The np.asarray syncs live in _read_waves (the sanctioned
        # completion-stage readback: one read a wave; with a Store the
        # wave's packed rows too). Sharded ("s") and replica ("r")
        # outputs materialize side by side; placements tag which list a
        # lane demuxes from.
        try:
            with tracing.stage("flush.readback", fs, fs.ids):
                s_rows, s_tot = (
                    _read_waves(t.outs, fs) if sw is None
                    else sw.read(t.outs, fs)
                )
                r_rows, r_tot = _read_waves(t.r_outs, fs)
                host = {"s": s_rows, "r": r_rows}
        finally:
            self.metrics.busy_exit()  # entered in _execute_waves
        t_sync = time.perf_counter()
        with tracing.stage("flush.post", fs, fs.ids):
            dev_s = t_sync - t.t_dev
            # Transfer ledger: the serve-path d2h readback. Duration is the
            # blocking sync (copy + any pending compute it waited on).
            _transfer.record(
                self.metrics, "d2h", "serve",
                _transfer.nbytes(host) + (sw.nbytes if sw is not None else 0),
                t_sync - t_c0,
            )

            tot = [a + b for a, b in zip(s_tot, r_tot)]
            dur = time.perf_counter() - t.t0
            em = self.metrics
            trace_id = (t.trace_id or "") if cfg.exemplars else ""
            em.observe(tot[0], tot[1], tot[2], tot[3], t.waves, t.served, dur)
            em.observe_flush(
                "object", t.served, t.waves, dur, dev_s, trace_id,
                collective=self.topo.n_dev > 1, transfers=(fs.h2d, fs.d2h),
                launches=fs.launches, programs=fs.programs,
                crossings=fs.crossings, calls=fs.calls,
                sequence=fs.sequence,
            )
            em.observe_stage("assemble", t.t_dev - t.t0)
            # `dispatch` (the launches under the lock) and `lock_wait` were
            # observed by _execute_waves; together they are the interval
            # t_dev..t_disp_end this line observed as `dispatch` before.
            em.observe_stage("inflight_wait", max(t_c0 - t.t_disp_end, 0.0))
            em.observe_stage("device_sync", t_sync - t_c0)
            # The record shares fs.us, which publish() fills in below.
            em.recorder.record(
                path="object", layout=cfg.layout, n=t.served, waves=t.waves,
                carry=t.carry_n, widths=t.widths,
                dur_us=int(dur * 1e6), dev_us=int(dev_s * 1e6),
                ticket=t.seq, trace_id=t.trace_id or "",
                call=fs.ids["call"], calls=fs.calls, stages_us=fs.us,
                sequence=fs.sequence,
            )

            # Write-behind BEFORE resolving futures, so a caller that observed
            # its response can rely on the store reflecting it (the reference's
            # OnChange runs within the request, algorithms.go:149-153).
            if sw is not None:
                with tracing.stage("flush.write_behind", fs, fs.ids):
                    self._store_write_behind(
                        t.items, t.placements, s_rows, sw.rows
                    )
                # the next flush's turn at the Store (on a failure:
                # _complete_ticket)
                sw.release()
                # Hygiene after the write-behind, as on the columnar
                # path: a key that loses its string is prefetched from
                # the Store by its next flush, outside both locks, and
                # must find this flush's change there.
                if cfg.keep_key_strings:
                    self._drop_displaced_strings(sw.events)

            # GUBER_STAGE_METADATA: the flush-level stage times every served
            # item shares, built once; each response appends its own queue
            # wait (resolve time is unknowable before resolution and is
            # reported as the flush-level histogram only).
            stage_base = None
            if self._stage_md:
                stage_base = (
                    f"assemble={int((t.t_dev - t.t0) * 1e6)}"
                    f",dispatch={int((t.t_disp_end - t.t_dev) * 1e6)}"
                    f",inflight_wait={int(max(t_c0 - t.t_disp_end, 0.0) * 1e6)}"
                    f",device_sync={int((t_sync - t_c0) * 1e6)}"
                )
            hk = em.hotkeys if em.hotkeys.k > 0 else None
            hk_agg: Dict[Tuple[int, int], list] = {}
            # Standby dirty harvest rides the same demux loop as the hotkey
            # aggregation: zero extra passes, None when tracking is off.
            with raceguard.racy_read(
                "_dirty",
                reason="None-gate only; _note_dirty re-checks under the lock",
            ):
                dirty_agg: Optional[list] = (
                    [] if self._dirty is not None else None
                )
            OVER = 1  # api.types.Status.OVER_LIMIT
            for (req, fut), place in zip(t.items, t.placements):
                if place is None or place == "carry":
                    continue  # resolved (encode error) or deferred
                path, w, lane = place[0], place[1], place[2]
                hw = host[path][w]
                st, rem, rst, lim = (
                    hw[OUT_STATUS], hw[OUT_REMAINING], hw[OUT_RESET_TIME],
                    hw[OUT_LIMIT],
                )
                status = int(st[lane])  # guberlint: allow-host-sync -- numpy demux of already-materialized rows
                if dirty_agg is not None:
                    dirty_agg.append((req.hash_key(), max(int(req.hits), 0)))
                if hk is not None:
                    k = (place[3], place[4])
                    ent = hk_agg.get(k)
                    if ent is None:
                        hk_agg[k] = [
                            max(int(req.hits), 0), int(status == OVER),
                            req.hash_key(),
                        ]
                    else:
                        ent[0] += max(int(req.hits), 0)
                        ent[1] += int(status == OVER)
                md = None
                if stage_base is not None:
                    t_enq = getattr(fut, "t_enq", None)
                    md = {
                        "stage_breakdown_us": (
                            f"queue={int((t.t0 - t_enq) * 1e6)},{stage_base}"
                            if t_enq is not None
                            else stage_base
                        )
                    }
                fut.set_result(
                    RateLimitResp(
                        status=status,
                        limit=int(lim[lane]),  # guberlint: allow-host-sync -- numpy demux of already-materialized rows
                        remaining=int(rem[lane]),  # guberlint: allow-host-sync -- numpy demux of already-materialized rows
                        reset_time=int(rst[lane]),  # guberlint: allow-host-sync -- numpy demux of already-materialized rows
                        **({"metadata": md} if md else {}),
                    )
                )
            if hk is not None and hk_agg:
                hk.update(
                    [(k, v[0], v[1], v[2]) for k, v in hk_agg.items()]
                )
            if dirty_agg:
                self._note_dirty(dirty_agg)
            em.observe_stage("resolve", time.perf_counter() - t_sync)
            self._observe_overlap(t)
        fs.publish()


    @staticmethod
    def _snapshot_from_row(r, lane: int, key: str):
        """ItemSnapshot from one materialized gathered-row lane."""
        from gubernator_tpu.store.store import ItemSnapshot

        return ItemSnapshot(
            key=key,
            algorithm=int(r.algo[lane]),
            status=int(r.status[lane]),
            limit=int(r.limit[lane]),
            duration=int(r.duration[lane]),
            remaining=int(r.remaining[lane]),
            stamp=int(r.stamp[lane]),
            expire_at=int(r.expire_at[lane]),
            invalid_at=int(r.invalid_at[lane]),
            burst=int(r.burst[lane]),
        )

    # ---- columnar fast path (the serving edge; see service/fastpath.py) ----

    def check_columns(
        self,
        cols,
        now: Optional[int] = None,
        select: Optional[np.ndarray] = None,
        hashes: Optional[tuple] = None,
        call=tracing.NO_CALL,
    ):
        """Vectorized decide over wire columns: no per-item Python objects
        anywhere — hashing, wave/lane assignment, encoding, and response
        demux are all batch array ops. Returns (status, limit, remaining,
        reset_time) int arrays in request order, or None when this batch
        needs the object path (one wave would hold more lanes than
        batch_size, or the batch is empty). A key that comes more than
        max_waves times does not: its waves run as further launches of
        the same flush, under one hold of the engine lock
        (_assemble_column_waves, _upload), so the call is applied as one
        unit and no other flush's hits of that key land between its
        own. A Store does NOT force a fallback: the store
        path runs the object path's per-wave sequence here (probe ->
        read-through -> decide -> write-behind) with request objects
        built only for actual miss lanes.

        Semantics mirror encode_one/encode_rows + the pump's wave
        assembler exactly (equivalence is fuzz-tested against the object
        path in tests/test_fastpath.py): stable sorting by group gives
        each request its occurrence rank as its wave, preserving per-key
        request order; within a wave, groups are distinct, so scatters
        stay disjoint.

        The caller guarantees: no GLOBAL / DURATION_IS_GREGORIAN items,
        no per-item metadata, and validation already handled.

        `select` serves a SUBSET of the batch (the mixed-ownership edge:
        locally-owned lanes go columnar while the rest forward), with
        `hashes` = (hi, lo, grp) precomputed over the FULL batch so key
        bytes need no re-slicing. Results align with `select`'s order.
        `call` is the caller's tracing.CallRecord: its sequence number
        names this flush's stages in the flight recorder and a capture.

        **Group commit** (docs/architecture.md "Calls that share a
        flush"). A call that arrives while no columnar flush is in its
        host stage (taken, not yet launched: hash, waves, key
        dictionary, upload, engine lock, dispatch) is a flush of its
        own, waits for nobody, and its columns are not copied. A small
        call that arrives while one is (_may_join) joins the waiting
        batch and sleeps. When a flush has launched and left the engine
        lock (never when it has read), the batch's first call is woken
        as its leader: it takes the whole batch, merges the members'
        columns in arrival order (_merge_joined), runs _flush_columns
        once and hands each member its slice. The semantics are the
        pump's, which coalesces calls too: per-key order is arrival
        order, each member's own `now` rides its lanes as created_at,
        the flush's scalar `now` is the leader's. No window, timer or
        knob: the batch is what arrived while the previous flush was
        being assembled."""
        if cols.n == 0:
            return None
        if now is None:
            now = self.now_fn()
        fs = FlushStages(self.metrics, self._flush_seq(), call.seq)
        gate = self._gate
        me = None
        with gate.lock:
            if (
                gate.active and select is None
                and self._may_join(cols, gate.items)
            ):
                me = _Joined(cols, now, fs)
                gate.waiting.append(me)
                gate.items += cols.n
            else:
                gate.active += 1
                fs.in_host_stage = True
        if me is None:
            try:
                return self._flush_columns(cols, now, select, hashes, fs)
            finally:
                self._left_host_stage(fs)
        with tracing.stage("flush.join", fs, fs.ids):
            me.latch.acquire()
        if me.leads:
            return self._lead(me)
        fs.publish()  # its one stage: the wait
        if me.exc is not None:
            raise me.exc
        return me.out

    def _may_join(self, cols, waiting_items: int) -> bool:
        """Whether a call may wait for the next merged flush (under the
        gate's lock, and only while a flush is in its host stage):
        decided by what is observed, the call's own size and the warm
        shapes (_join_budget). A second call of its size must fit
        beside it, so a call too large to ever meet a peer goes
        straight through and waits for nobody; the batch must still
        hold it; and no item asks for NO_BATCHING, which skips the
        pump's window as well (_pump)."""
        budget = self._join_budget()
        n = cols.n
        return (
            2 * n <= budget and waiting_items + n <= budget
            and not (cols.behavior & int(Behavior.NO_BATCHING)).any()
        )

    def _left_host_stage(self, fs: FlushStages) -> None:
        """A columnar flush has launched and left the engine lock (or
        ended without a launch): its turn passes to the waiting batch's
        first call, which is woken to lead it, or lapses. Once a flush:
        _flush_columns calls it after its launch, check_columns and
        _lead again on every way out."""
        if not fs.in_host_stage:
            return
        fs.in_host_stage = False
        gate = self._gate
        head = None
        with gate.lock:
            if gate.waiting and not gate.waiting[0].leads:
                head = gate.waiting[0]
                head.leads = True  # `active` stays: the turn is its own now
            else:
                gate.active -= 1
        if head is not None:
            head.latch.release()

    def _lead(self, me: _Joined):
        """Serve the waiting batch that `me` heads, on me's thread: one
        _flush_columns over the merged columns, each member handed its
        slice and woken. A batch of one is served as it stands. A
        merged assembly is never refused: one key more than max_waves
        times across the members is more launches of the one flush
        (_assemble_column_waves), and a batch holds no more items than
        the narrowest warm width has lanes (_join_budget), so no wave
        outgrows batch_size. Were one refused all the same, nothing
        would have committed, and every member would go to the object
        path with its `out` still None. An exception out of the merged
        flush reaches every member (TableCommittedError must; any other
        sends each to the object path, service/fastpath.py)."""
        gate = self._gate
        fs = me.fs
        with gate.lock:
            batch, gate.waiting, gate.items = gate.waiting, [], 0
        fs.in_host_stage = True  # the turn it was handed with its promotion
        try:
            if len(batch) == 1:
                return self._flush_columns(me.cols, me.now, None, None, fs)
            fs.calls = len(batch)
            try:
                out = self._flush_columns(
                    _merge_joined(batch), me.now, None, None, fs
                )
            except BaseException as e:
                for m in batch[1:]:
                    m.exc = e
                raise
            if out is not None:
                lo = 0
                for m in batch:
                    hi = lo + m.cols.n
                    m.out = tuple(a[lo:hi] for a in out)
                    lo = hi
            return me.out
        finally:
            self._left_host_stage(fs)
            for m in batch[1:]:
                m.latch.release()

    def _flush_columns(self, cols, now: int, select, hashes, fs: FlushStages):
        """One columnar flush over `cols`: check_columns' body, for a
        lone call's columns or a batch's merged ones."""
        from gubernator_tpu import native as _native

        cfg = self.cfg
        store = self.store
        t_start = time.perf_counter()

        if hashes is None:
            with tracing.stage("flush.hash", fs, fs.ids):
                hi, lo, grp = _native.hash128_batch_raw(
                    cols.key_data.tobytes(), cols.key_offsets, cfg.num_groups
                )
        else:
            hi, lo, grp = hashes
        if self._rtier is not None:
            # Replica topologies serve GLOBAL columns internally: split
            # the batch between the sharded decide and the replica tier
            # (routes_global_internally — the caller does NOT filter
            # GLOBAL out for this engine).
            return self._check_columns_replica_split(
                cols, now, select, (hi, lo, grp), t_start, fs
            )
        # Key strings resolve through the ORIGINAL columns (select drops
        # key_offsets); the store path decodes every key, the store-less
        # path only never-seen ones (record_columnar_keys).
        orig_cols, sel_map = cols, None
        if select is not None and len(select) == 0:
            return None
        with tracing.stage("flush.waves", fs, fs.ids):
            if select is not None:
                hi, lo, grp = hi[select], lo[select], grp[select]
                cols = _select_columns(cols, select)
                sel_map = select
            asm = _assemble_column_waves(
                cols, hi, lo, grp, now, cfg.batch_size, cfg.max_waves,
                # Width bucketing uses only ALREADY-WARM shapes (batch_size
                # always is). With a store, only batch_size-wide store-path
                # kernels are warmed (warm_store_path); narrower buckets
                # would cold-compile probe/inject/gather under the lock.
                width_candidates=self._warm_shapes if store is None else (),
                stacked_widths={b for _d, b in self._warm_stacks},
            )
        if asm is None:
            fs.publish()  # the refused attempt's hash and waves
            return None
        n = cols.n
        wave_slices, wave, lane, ix, W, B = asm

        def key_str(j: int) -> str:
            return orig_cols.key_string(
                int(sel_map[j]) if sel_map is not None else j
            )

        # Store path pre-work (the columnar twin of _process's read-through
        # plumbing), shared with the replica-split path.
        prefetched: Dict[Tuple[int, int], object] = {}
        lane_reqs: List[Dict[int, tuple]] = [{} for _ in range(W)]
        resolver = wb_entries = None
        reads_through = False
        with tracing.stage("flush.keydict", fs, fs.ids):
            if store is not None:
                prefetched, lane_reqs, resolver, wb_entries, reads_through = (
                    self._store_columns_prework(
                        orig_cols, sel_map, hi, lo, wave, lane, W
                    )
                )
            elif cfg.keep_key_strings and cfg.record_columnar_keys:
                # Store-less columnar edge: keep the key-string dictionary
                # complete so handover/Loader snapshots are routable
                # (docs/robustness.md "Rolling restarts & handover" — an
                # anonymous row cannot be ring-placed at its new owner).
                # Cost discipline: a bulk (hi, lo) membership probe, and
                # string decodes ONLY for never-seen keys — steady-state
                # traffic pays dict lookups, not Python string builds.
                keys_l = list(zip(hi.tolist(), lo.tolist()))
                with self._keys_lock:
                    miss = [
                        (j, k)
                        for j, k in enumerate(keys_l)
                        if k not in self._key_strings
                    ]
                if miss:
                    decoded = [(k, key_str(j)) for j, k in miss]
                    with self._keys_lock:
                        self._key_strings.update(decoded)
                    self._maybe_prune_key_strings()

        with tracing.stage("flush.waves", fs, fs.ids):
            ops = self._upload(wave_slices, now, fs, stack=not reads_through)
        _telemetry.set_shape_hint(f"{cfg.layout}:columnar:{W}x{B}")
        t_dev = time.perf_counter()
        with _telemetry.serving_scope(self.metrics), tracing.span(
            "engine.flush", level="DEBUG", path="columnar", items=n, waves=W,
            layout=cfg.layout,
        ) as fspan:
            outs, _r_outs, sw = self._execute_waves(
                wave_slices, ops, lane_reqs, now, prefetched, fs,
                req_resolver=resolver,
            )
            self._left_host_stage(fs)  # launched: the next batch's turn

            try:
                with tracing.stage(
                    "flush.readback", fs, fs.ids
                ), _transfer.account(self.metrics, "d2h", "serve") as tx:
                    if sw is None:
                        out_rows, totals = _read_waves(outs, fs)
                    else:
                        out_rows, totals = sw.read(outs, fs)
                        tx.add(sw.nbytes)
                    tx.add(out_rows)
            finally:
                self.metrics.busy_exit()  # entered in _execute_waves
        dev_s = time.perf_counter() - t_dev
        flush_trace_id = tracing.trace_id_of(fspan)

        with tracing.stage("flush.post", fs, fs.ids):
            if sw is not None:
                self._store_columns_postwork(fs, wb_entries, out_rows, sw)

            tot_hits, tot_miss, tot_evic, tot_over = totals
            dur = time.perf_counter() - t_start
            em = self.metrics
            em.observe(tot_hits, tot_miss, tot_evic, tot_over, W, n, dur)
            em.observe_flush(
                "columnar", n, W, dur, dev_s,
                flush_trace_id if cfg.exemplars else "",
                collective=self.topo.n_dev > 1, transfers=(fs.h2d, fs.d2h),
                launches=fs.launches, programs=fs.programs,
                crossings=fs.crossings, calls=fs.calls,
                sequence=fs.sequence,
            )
            em.observe_stage("assemble", t_dev - t_start)
            em.observe_stage("device_sync", dev_s)
            if W > cfg.max_waves:
                em.flushes_over_max_waves.inc()
            em.recorder.record(
                path="columnar", layout=cfg.layout, n=n, waves=W, carry=0,
                widths=[w.lanes for w in wave_slices],
                launches=fs.launches, dur_us=int(dur * 1e6),
                dev_us=int(dev_s * 1e6), trace_id=flush_trace_id,
                ticket=fs.ids["flush"], call=fs.ids["call"],
                calls=fs.calls, stages_us=fs.us,
                sequence=fs.sequence,
            )
            st_req, r_limit, remaining, reset_time = _demux_lanes(
                out_rows, ix
            )
            if em.hotkeys.k > 0:
                _note_hotkeys_columnar(em.hotkeys, hi, lo, cols.hits, st_req)
            with raceguard.racy_read(
                "_dirty",
                reason="None-gate only; _note_dirty re-checks under the lock",
            ):
                track_dirty = self._dirty is not None
            if track_dirty:
                self._note_dirty_columnar(hi, lo, cols.hits)
            out = (st_req, r_limit, remaining, reset_time)
        fs.publish()
        return out

    def _store_columns_prework(self, orig_cols, idx_map, hi, lo, wave, lane, W):
        """What a columnar flush with a Store needs before its waves run,
        for the items (hi, lo, wave, lane) of one assembly: request
        objects are built LAZILY, only for miss lanes; key strings are
        decoded once for the dictionary and the write-behind; never-seen
        keys prefetch OUTSIDE the device lock. `idx_map` maps an item to
        its place in `orig_cols` (None: the items are `orig_cols`'
        own, in order; a selection drops key_offsets, so strings resolve
        through the original columns). Returns (prefetched, per-wave
        {lane: (item, hi, lo)}, the item -> request resolver, the
        write-behind's (key, wave, lane, hi, lo) entries in request
        order, whether the flush already knows it reads through: a key
        this process has never seen, in the Store or not, is not in
        the table)."""
        from gubernator_tpu import wire as _wire

        cfg = self.cfg
        if idx_map is None:
            strs = orig_cols.key_strings_all()
        else:
            idx_l = idx_map.tolist()
            strs = [orig_cols.key_string(i) for i in idx_l]

        def req_of(j: int) -> RateLimitReq:
            return _wire.req_from_columns(
                orig_cols, j if idx_map is None else idx_l[j]
            )

        # One-shot tolist conversions: per-item numpy scalar boxing
        # (int(hi[j]) etc.) dominated this path's host cost.
        hi_l, lo_l = hi.tolist(), lo.tolist()
        wave_l, lane_l = wave.tolist(), lane.tolist()
        keys_l = list(zip(hi_l, lo_l))
        prefetched: Dict[Tuple[int, int], object] = {}
        need: list = []
        if cfg.keep_key_strings:
            # Prefetch never-seen keys OUTSIDE the lock (the dict is
            # a superset of table residency, as in _process). Without
            # the dictionary there is no never-seen predicate: rely
            # on the in-lock per-wave probe alone rather than issuing
            # a blocking store.get for every key of every flush.
            seen = set()
            with self._keys_lock:
                for j, k in enumerate(keys_l):
                    if k not in self._key_strings and k not in seen:
                        seen.add(k)
                        need.append((j, k))
                self._key_strings.update(zip(keys_l, strs))
            for j, k in need:
                snap = self._store_get(req_of(j))
                if snap is not None:
                    prefetched[k] = snap
            self.metrics.observe_store_gets(
                len(prefetched), len(need) - len(prefetched)
            )
            self._maybe_prune_key_strings()
        # the lazy lane_req dicts: item indices, resolved only on a miss
        lane_reqs: List[Dict[int, tuple]] = [{} for _ in range(W)]
        for j, w_ in enumerate(wave_l):
            lane_reqs[w_][lane_l[j]] = (j, hi_l[j], lo_l[j])
        return (
            prefetched, lane_reqs, req_of,
            list(zip(strs, wave_l, lane_l, hi_l, lo_l)), bool(need),
        )

    def _store_columns_postwork(self, fs, wb_entries, out_rows, sw):
        """What a columnar flush with a Store does after its read, before
        the call returns: write-behind from the per-wave gathered rows
        (last-op-wins per key, request order), the end of its turn at
        the Store, then key-dictionary hygiene — same semantics as the
        object path's flush."""
        try:
            if wb_entries:
                with tracing.stage("flush.write_behind", fs, fs.ids):
                    self._store_write_behind_core(
                        wb_entries, out_rows, sw.rows
                    )
        finally:
            sw.release()
        if self.cfg.keep_key_strings:
            self._drop_displaced_strings(sw.events)

    def _check_columns_replica_split(
        self, cols, now, select, hashes, t_start, fs
    ):
        """Columnar serving for replica topologies — the multi-chip
        daemon's fast edge. Non-GLOBAL items feed the owner-sharded SPMD
        decide (shared wave assembler, one collective call per wave);
        GLOBAL items feed the per-device replica tier with the same
        round-robin home assignment as the object path (replica decide
        handles pending bookkeeping internally; the GLOBAL bit stays SET
        — this engine routes_global_internally). Waves always run at the
        full batch width — a narrower width would cold-compile a second
        SPMD program per shape.

        With a Store the sharded tier's waves run the Store's per-wave
        sequence, as on every other path (_execute_waves), and the
        write-behind is handed the sharded tier's lanes alone: GLOBAL
        buckets of the replica tier are never persisted (the object
        path's _store_write_behind says the same: `tag != "s"`)."""
        cfg = self.cfg
        store = self.store
        hi, lo, grp = hashes
        if select is not None and len(select) == 0:
            return None
        orig_cols = cols
        with tracing.stage("flush.waves", fs, fs.ids):
            asm = self._assemble_replica_split(
                cols, now, select, hi, lo, grp
            )
        if asm is None:
            fs.publish()
            return None
        (cols, hi, lo, s_asm, r_asm, ng_idx, g_idx, wave_slices,
         r_slices) = asm
        n = cols.n
        prefetched: Dict[Tuple[int, int], object] = {}
        lane_reqs: List[Dict[int, tuple]] = [{} for _ in wave_slices]
        resolver = wb_entries = None
        reads_through = False
        if store is not None and s_asm is not None:
            with tracing.stage("flush.keydict", fs, fs.ids):
                idx_map = ng_idx if select is None else select[ng_idx]
                if select is None and len(g_idx) == 0:
                    idx_map = None  # the call's own columns, in order
                prefetched, lane_reqs, resolver, wb_entries, reads_through = (
                    self._store_columns_prework(
                        orig_cols, idx_map, hi[ng_idx], lo[ng_idx],
                        s_asm[1], s_asm[2], s_asm[4],
                    )
                )
        with tracing.stage("flush.waves", fs, fs.ids):
            ops = self._upload(wave_slices, now, fs, stack=not reads_through)
            r_ops = self._upload(r_slices, now, fs, stack=False)

        _telemetry.set_shape_hint(
            f"{cfg.layout}:mesh-columnar:B{cfg.batch_size}"
        )
        t_dev = time.perf_counter()
        with _telemetry.serving_scope(self.metrics), tracing.span(
            "engine.flush", level="DEBUG", path="columnar", items=n,
            layout=cfg.layout,
        ) as fspan:
            # _execute_waves supplies the lock, the collective guard,
            # page residency (paged mesh), and unified recovery.
            s_outs, r_outs, sw = self._execute_waves(
                wave_slices, ops, lane_reqs, now, prefetched, fs,
                req_resolver=resolver, r_ops=r_ops,
            )
            self._left_host_stage(fs)  # launched: the next batch's turn

        status = np.zeros(n, np.int64)
        r_limit = np.zeros(n, np.int64)
        remaining = np.zeros(n, np.int64)
        reset_time = np.zeros(n, np.int64)
        waves_total = 0
        tots = [0, 0, 0, 0]
        try:
            with tracing.stage(
                "flush.readback", fs, fs.ids
            ), _transfer.account(self.metrics, "d2h", "serve") as tx:
                s_rows = None  # the sharded tier's, for the write-behind
                for outs, asm, idx, tier_sw in (
                    (s_outs, s_asm, ng_idx, sw), (r_outs, r_asm, g_idx, None),
                ):
                    if asm is None:
                        continue
                    if tier_sw is None:
                        out_rows, totals = _read_waves(outs, fs)
                    else:
                        out_rows, totals = tier_sw.read(outs, fs)
                        s_rows = out_rows
                        tx.add(tier_sw.nbytes)
                    tx.add(out_rows)
                    (status[idx], r_limit[idx], remaining[idx],
                     reset_time[idx]) = _demux_lanes(out_rows, asm[3])
                    waves_total += asm[4]
                    for j, v in enumerate(totals):
                        tots[j] += v
        except BaseException:
            if sw is not None:
                sw.release()  # the replica tier's read failed after sw's
            raise
        finally:
            self.metrics.busy_exit()  # entered in _execute_waves
        dev_s = time.perf_counter() - t_dev
        with tracing.stage("flush.post", fs, fs.ids):
            if sw is not None:
                self._store_columns_postwork(fs, wb_entries, s_rows, sw)
            dur = time.perf_counter() - t_start
            flush_trace_id = tracing.trace_id_of(fspan)
            em = self.metrics
            em.observe(
                tots[0], tots[1], tots[2], tots[3], waves_total, n, dur
            )
            em.observe_flush(
                "columnar", n, waves_total, dur, dev_s,
                flush_trace_id if cfg.exemplars else "",
                collective=self.topo.n_dev > 1, transfers=(fs.h2d, fs.d2h),
                launches=fs.launches, programs=fs.programs,
                crossings=fs.crossings, calls=fs.calls,
                sequence=fs.sequence,
            )
            em.observe_stage("assemble", t_dev - t_start)
            em.observe_stage("device_sync", dev_s)
            if any(
                a is not None and a[4] > cfg.max_waves
                for a in (s_asm, r_asm)
            ):
                em.flushes_over_max_waves.inc()
            em.recorder.record(
                path="columnar", layout=cfg.layout, n=n, waves=waves_total,
                carry=0, widths=[cfg.batch_size] * waves_total,
                launches=fs.launches,
                dur_us=int(dur * 1e6), dev_us=int(dev_s * 1e6),
                trace_id=flush_trace_id, ticket=fs.ids["flush"],
                call=fs.ids["call"], calls=fs.calls, stages_us=fs.us,
                sequence=fs.sequence,
            )
            if em.hotkeys.k > 0:
                _note_hotkeys_columnar(em.hotkeys, hi, lo, cols.hits, status)
        fs.publish()
        return (status, r_limit, remaining, reset_time)

    def _assemble_replica_split(self, cols, now, select, hi, lo, grp):
        """The host assembly of _check_columns_replica_split: the
        sharded and the replica waves and their per-wave slices, or
        None where the batch needs the object path."""
        cfg = self.cfg
        rt = self._rtier
        if select is not None:
            hi, lo, grp = hi[select], lo[select], grp[select]
            cols = _select_columns(cols, select)
        g_mask = (np.asarray(cols.behavior) & int(Behavior.GLOBAL)) != 0  # guberlint: allow-host-sync -- wire columns are host numpy (wire.parse_requests output), no device readback
        ng_idx = np.nonzero(~g_mask)[0]
        g_idx = np.nonzero(g_mask)[0]

        # -- assemble the sharded (non-GLOBAL) waves --
        s_asm = None
        if len(ng_idx):
            s_cols = (
                cols if len(g_idx) == 0 else _select_columns(cols, ng_idx)
            )
            s_asm = _assemble_column_waves(
                s_cols, hi[ng_idx], lo[ng_idx], grp[ng_idx], now,
                cfg.batch_size, cfg.max_waves,
            )
            if s_asm is None:
                return None

        # -- assemble the replica (GLOBAL) waves --
        r_asm = None
        if len(g_idx):
            r_cols = _select_columns(cols, g_idx)
            r_lo = lo[g_idx]
            slot = (r_lo.astype(np.uint64) % np.uint64(rt.num_rgroups)
                    ).astype(np.int64)
            with self._lock:  # round-robin base, racing the pump thread
                rr0 = self._home_rr
                self._home_rr += len(g_idx)
            homes = (rr0 + np.arange(len(g_idx))) % self.topo.n_dev
            # Wave conflicts are per (home, slot) PAIR (the object path's
            # place key): the pair is the assembly's conflict key, the
            # slot the batch's group column.
            pair = homes * np.int64(rt.num_rgroups) + slot
            r_asm = _assemble_column_waves(
                r_cols, hi[g_idx], r_lo, pair, now,
                cfg.batch_size, cfg.max_waves, group=slot, home=homes,
            )
            if r_asm is None:
                return None
            self._note_replica_decisions(
                np.bincount(homes, minlength=self.topo.n_dev)
            )

        wave_slices = s_asm[0] if s_asm is not None else []
        r_slices = r_asm[0] if r_asm is not None else []
        return (cols, hi, lo, s_asm, r_asm, ng_idx, g_idx, wave_slices,
                r_slices)

    def _execute_waves(
        self, waves, ops, lane_reqs, now, prefetched, fs, req_resolver=None,
        r_ops=(),
    ):
        """Run decide over scatter-disjoint waves under the device lock,
        with the store's sequence when a Store is attached, wave by wave:
        probe (cache lookup) -> Store.Get for misses -> insert -> decide
        -> gather touched rows (reference algorithms.go:45-51, 149-153 —
        the gathered rows let write-behind persist the value the caller
        observed even if a later wave displaces the slot).

        waves: the host WaveOperands (the pager, the shard attribution
        and the store's probe read their columns); ops: the same waves
        as _upload left them on the device, [(first wave, waves,
        operand)]: ONE array and ONE launch a run — the only operand of
        a launch beside the device-resident table, so nothing crosses to
        the device under the lock. A run of several waves is applied in
        order inside its one program and commits whole or not at all;
        with a pager every run is one wave (_upload), so its per-wave
        sequence below is the whole loop. **With a Store a run is
        probed once, decided once and gathered once** (_probe_run)
        where the one probe finds every lane live: no wave of such a
        run inserts, so nothing is displaced, no later wave can miss
        and no inject can be due, which is all the per-wave sequence is
        there for; a run with a lane that is not live is handed back
        wave by wave, on the device (ops/layout.py operand_waves), and
        runs the per-wave sequence. lane_reqs: per-wave
        {lane: (req_or_index, key_hi, key_lo)}; with req_resolver set,
        the first element is an index resolved lazily (columnar path).
        r_ops: the uploaded GLOBAL replica waves (replica topologies
        only; their per-lane home device rides the operand), decided
        against the replica tier after the sharded waves, wave by
        wave. Returns (outs, r_outs, store_waves): one (output, waves)
        a launch for _read_waves, still on the device, and with a Store
        the flush's _StoreWaves (None without one): its launches' packed
        rows, on the device too, and the hand-over lock, which the
        caller releases after its write-behind (_StoreWaves.read in its
        `flush.readback`, then _store_columns_postwork or _complete).

        `fs` (FlushStages) takes the two stages every path shares:
        `flush.lock_wait` (waiting for the engine lock and the
        collective guard) and `flush.dispatch` (the launches under the
        lock). Asking for the lock opens the engine's busy interval;
        the caller closes it (EngineMetrics.busy_exit) when its readback
        ends; a failure here closes it before it propagates.

        All dispatches run under the topology's collective guard (inside
        the table lock): on a mesh, concurrent multi-device programs
        from another engine in the same process would interleave
        per-device enqueues and deadlock in the collective rendezvous.

        On failure: keeps the last valid intermediate state if still
        held; a failed jitted call may have consumed the donated table
        buffers, in which case recovery rebuilds an empty table so the
        engine keeps serving (counter loss on failure matches the
        reference's accepted cache-loss-on-restart semantics,
        docs/architecture.md:5-11). If waves already committed to a
        SURVIVING table, raises TableCommittedError so no caller retries
        the batch through another path (double-apply)."""
        store = self.store
        cfg = self.cfg
        rt = self._rtier
        outs: List[object] = []
        r_outs: List[object] = []
        served: Dict[Tuple[int, int], Tuple[int, int]] = {}  # key->(w,lane)
        sw = None
        if store is not None:
            sw = _StoreWaves(lane_reqs, self._handover, self.metrics)
        if self.topo.n_dev > 1:
            # Shard-skew attribution (docs/monitoring.md "SLOs & burn
            # rates"): host-side bincount over the waves' group arrays
            # BEFORE device dispatch — this is the one choke point both
            # the object and columnar paths flow through.
            self._note_shard_decisions([w.batch for w in waves])
        # Under the lock every microsecond is serial for all callers, so
        # it holds the stages' two clock reads and nothing else of them:
        # the intervals go to `fs` after the release. Only a capture or
        # a DEBUG SDK (`live`, None otherwise) opens spans in there.
        n_dispatch = len(ops) + len(r_ops)
        fs.launches += n_dispatch
        if store is not None and fs.programs is None:
            fs.programs = dict.fromkeys(STORE_WAVE_PROGRAMS, 0)
            fs.crossings = [0, 0]
        self.metrics.busy_enter()
        live = tracing.open_live("flush.lock_wait", fs.ids)
        t_wait = time.perf_counter_ns()
        with self._lock, self.topo.dispatch_guard():
            t_in = time.perf_counter_ns()
            if live is not None:
                tracing.close_live(live)
                live = tracing.open_live(
                    "flush.dispatch", dict(fs.ids, waves=n_dispatch)
                )
            table = self.table
            rstate = rt.state if rt is not None else None
            unread = 0  # launches since the last one that was waited for
            todo = collections.deque(ops)
            try:
                while todo:
                    w, n, op = todo.popleft()
                    wo = waves[w]
                    if self._pager is not None:
                        # Promote every page this wave touches BEFORE
                        # its probe/decide (a probe-miss against a
                        # demoted page must resolve against promoted
                        # state, not the sentinel). Same lock as the
                        # decide: a promotion can never race a flush.
                        wb = wo.batch
                        table = self._pager.ensure_resident(
                            table,
                            self._pager.touched_pages(wb.group, wb.active),
                        )
                    if store is not None and n > 1:
                        with tracing.stage("flush.readthrough", fs, fs.ids):
                            if not self._probe_run(
                                table, op, lane_reqs[w:w + n], fs
                            ):
                                # a lane to read through: the run's
                                # waves one by one, from here on
                                fs.launches += n - 1
                                todo.extendleft(reversed([
                                    (w + i, 1, o) for i, o in
                                    enumerate(operand_waves(op)[:n])
                                ]))
                                continue
                    elif store is not None:
                        with tracing.stage("flush.readthrough", fs, fs.ids):
                            table = self._wave_readthrough(
                                table, op, wo.batch, lane_reqs[w], now,
                                prefetched, served, sw, fs,
                                req_resolver=req_resolver,
                            )
                    table, out = self.K.decide_packed(
                        table, op, cfg.ways, store is not None
                    )
                    if store is not None:
                        # The row gather (a program of its own,
                        # K.gather_rows) takes its slot column from the
                        # decide's output on the device, so the two are
                        # launched back to back, nothing uploaded. The
                        # launch's output and its packed rows are
                        # not read here: nothing under the lock needs
                        # them but a later wave whose key was displaced
                        # since (sw.host_rows). Their host copies start
                        # now and the flush reads them after the
                        # release (_StoreWaves.read). A stacked run's
                        # gather comes after its last wave and shows
                        # each key's final row, which is what the
                        # write-behind persists (last op per key); with
                        # nothing displaced it is the row after the
                        # key's last wave.
                        fs.programs["decide"] += 1
                        fs.programs["gather_rows"] += 1
                        with tracing.stage("flush.store_rows", fs, fs.ids):
                            rows = self.K.gather_rows(table, out, True)
                            out.copy_to_host_async()
                            rows.copy_to_host_async()
                        sw.add(w, n, rows)
                        if n > 1:
                            sw.pre += [[] for _ in range(n)]
                        for k in range(w, w + n):
                            for lane, entry in lane_reqs[k].items():
                                served[(entry[1], entry[2])] = (k, lane)
                    outs.append((out, n))
                    unread = self._bound_in_flight(out, unread)
                for _w, n, op in r_ops:
                    rstate, out = rt.decide(rstate, op)
                    r_outs.append((out, n))
                    unread = self._bound_in_flight(out, unread)
                self.table = table
                if rt is not None:
                    rt.state = rstate
                if sw is not None:
                    fs.sequence = (
                        "stacked"
                        if sw.runs and all(n > 1 for _w, n, _r in sw.runs)
                        else "per_wave"
                    )
                    # the flush's turn at the Store, taken in the order
                    # of the engine lock (waits only while the flush
                    # before it is still handing over)
                    sw.take()
            except Exception as e:
                self.metrics.busy_exit()  # no readback will follow
                if live is not None:
                    tracing.close_live(live)
                if sw is not None:
                    sw.release()  # taken for a Store.get, if at all
                self.table = table
                if rt is not None:
                    rt.state = rstate
                rebuilt = self._recover_table_locked()
                if (outs or r_outs) and not rebuilt:
                    raise TableCommittedError(str(e)) from e
                raise
            t_out = time.perf_counter_ns()
            if live is not None:
                tracing.close_live(live)
        fs.add("lock_wait", t_wait, t_in)
        fs.add("dispatch", t_in, t_out)
        return outs, r_outs, sw

    def _probe_run(self, table, op, lane_reqs, fs: FlushStages) -> bool:
        """Under the engine lock, a Store flush's stacked run: ONE
        probe over the run's operand (the same program at a (depth,
        rows, B) shape: the table is only read, so the waves are
        independent) and the one blocking read, a byte a lane. Whether
        every lane of the run is live in the table at the flush's
        `now`: `lane_reqs` are the run's waves' and hold every active
        lane, and the probe answers False for a padding lane, so the
        live lanes are all of them exactly when they are as many."""
        fs.programs["probe"] += 1
        found = np.asarray(self.K.probe_exists(table, op, self.cfg.ways))  # guberlint: allow-host-sync -- store path: the run's one probe answer (a byte a lane), needed under the lock to choose the sequence
        fs.crossings[1] += 1
        return int(found.sum()) == sum(map(len, lane_reqs))

    def _bound_in_flight(self, out, unread: int) -> int:
        """Under the engine lock, after a launch whose output is `out`:
        no more than max_waves launches of one flush are in flight
        unread, the bound the pump's wave cap has always kept. A
        columnar call whose key comes more often than max_waves times
        may make more (a launch a wave on the replica tier, with a pager
        or where no stacked shape is warm), and a backend that holds a
        device's queue to a fixed depth (the CPU client: 32) then blocks
        the enqueue for one device of a mesh while the others wait for
        it in the collective. Returns the launches now unread."""
        unread += 1
        if unread < self.cfg.max_waves:
            return unread
        jax.block_until_ready(out)  # guberlint: allow-host-sync -- once in max_waves launches of one flush: bounds the device queue (see above)
        return 0

    def _drop_displaced_strings(self, events) -> None:
        """Key-dictionary hygiene (store path): a key whose LAST flush
        event was a displacement is gone from the table — drop its string
        so its next request prefetches store state OUTSIDE the device
        lock. A key re-inserted after its displacement (read-through or a
        later wave) keeps its entry; Loader snapshots need strings for
        every live key. Read-through correctness never depends on this —
        the per-wave probe is ground truth."""
        if not events:
            return
        last: Dict[Tuple[int, int], str] = {}
        for ev, k in events:
            last[k] = ev
        dead = [k for k, ev in last.items() if ev == "d"]
        if dead:
            with self._keys_lock:
                for k in dead:
                    self._key_strings.pop(k, None)

    def _wave_readthrough(
        self,
        table,
        op,
        wb,
        lane_req: Dict[int, tuple],
        now,
        prefetched: Dict,
        served: Dict,
        sw: _StoreWaves,
        fs: FlushStages,
        req_resolver=None,
    ):
        """Reference miss path at wave granularity: probe the table for
        each lane's key (the probe reads `op`, the wave's operand that
        _upload put on the device before the lock; its answer is the one
        array read here: Store.get and the inject depend on it); for
        actual misses, recover the freshest state and inject it so the
        wave's decide continues the counter (reference
        algorithms.go:45-51). Freshness order:

        1. a row this SAME flush already decided (the key was displaced
           between its own waves — pre-flush store state would drop the
           earlier hits, and a RESET-freed row must stay gone because the
           store.remove only lands at flush end). That wave's packed
           rows are still on the device: they are read here, under the
           lock, the one case in which they are (a crossing);
        2. the pre-flush prefetch (keys never seen by this process);
        3. Store.Get under the lock (rare: displaced in a prior flush but
           raced back before hygiene dropped its string). The flush
           takes its turn at the Store first (sw.take), so the answer
           holds every earlier flush's write-behind.

        The events of this wave's inject go to `sw.pre`, one list a wave.

        Runs under self._lock; store outages degrade to misses, never
        table-fatal."""
        from gubernator_tpu.ops.inject import InjectBatch

        cfg = self.cfg
        fs.programs["probe"] += 1
        exists = np.asarray(self.K.probe_exists(table, op, cfg.ways))  # guberlint: allow-host-sync -- store path: the probe's answer (a byte a lane), needed under the lock before Store.get and the inject
        fs.crossings[1] += 1
        events: list = []
        sw.pre.append(events)
        rows = []
        gets = hits = from_store = 0
        for lane, (req, hi, lo) in lane_req.items():
            if exists[lane]:
                continue
            if req_resolver is not None:
                # Columnar path: lane_req carries item indices; request
                # objects are built lazily, only for actual misses
                # (steady state has none).
                req = req_resolver(req)
            snap = None
            sv = served.get((hi, lo))
            if sv is not None:
                pw, plane = sv
                r, read_now = sw.host_rows(pw)
                if read_now:
                    fs.crossings[1] += 1
                if (
                    bool(r.used[plane])
                    and int(r.key_hi[plane]) == hi
                    and int(r.key_lo[plane]) == lo
                ):
                    snap = self._snapshot_from_row(r, plane, req.hash_key())
                # else: that wave freed the entry (RESET_REMAINING) — it
                # must look absent; do NOT fall back to the stale store.
            else:
                snap = prefetched.get((hi, lo))
                if snap is None:
                    sw.take()
                    snap = self._store_get(req)
                    gets += 1
                    hits += snap is not None
                from_store += snap is not None
            if snap is not None:
                rows.append((lane, snap, hi, lo))
        self.metrics.observe_store_gets(hits, gets - hits)
        if not rows:
            return table
        fs.programs["inject"] += 1
        self.metrics.store_injected_rows.inc(from_store)
        ib = InjectBatch.zeros(cfg.batch_size)
        for j, (lane, s, hi, lo) in enumerate(rows):
            ib.key_hi[j] = hi
            ib.key_lo[j] = lo
            ib.group[j] = wb.group[lane]
            ib.algo[j] = int(s.algorithm)
            ib.status[j] = int(s.status)
            ib.limit[j] = s.limit
            ib.duration[j] = s.duration
            ib.remaining[j] = s.remaining
            ib.stamp[j] = s.stamp
            ib.expire_at[j] = s.expire_at
            ib.invalid_at[j] = int(getattr(s, "invalid_at", 0))
            ib.burst[j] = s.burst
            ib.active[j] = True
        with _transfer.account(self.metrics, "h2d", "inject") as tx:
            table, ehi, elo = self.K.inject(table, ib, now, cfg.ways)
            tx.add(ib)
        ehi = np.asarray(ehi)  # guberlint: allow-host-sync -- store path, a wave with a miss only: the keys the inject displaced
        elo = np.asarray(elo)  # guberlint: allow-host-sync -- as ehi
        fs.crossings[0] += len(ib)  # the struct operand, field by field
        fs.crossings[1] += 2
        for j in np.nonzero((ehi != 0) | (elo != 0))[0]:
            events.append(("d", (int(ehi[j]), int(elo[j]))))
        for lane, snap, hi, lo in rows:
            events.append(("i", (hi, lo)))
        return table

    def _store_get(self, req):
        """Store.get for a read-through; a Store that raises is a cache
        miss, never a crash and never table-fatal."""
        try:
            return self.store.get(req)
        except Exception:
            return None

    def _store_write_behind(self, items, placements, out_rows, rows) -> None:
        def seq():
            for (req, _), place in zip(items, placements):
                if place is None or place == "carry":
                    continue
                tag, w, lane, hi, lo = place
                if tag != "s":
                    continue  # replica lanes never persist to a Store
                yield req.hash_key(), w, lane, hi, lo

        self._store_write_behind_core(seq(), out_rows, rows)

    _WB_FIELDS = (
        "used", "key_hi", "key_lo", "algo", "status", "limit", "duration",
        "remaining", "stamp", "expire_at", "invalid_at", "burst",
    )

    def _store_write_behind_core(self, seq, out_rows, rows) -> None:
        """seq yields (hash_key, wave, lane, hi, lo) in REQUEST order;
        out_rows are the waves' output rows as _read_waves gives them.

        Rows were gathered per-wave from the intermediate tables (and
        already materialized), so each lane sees exactly the state its
        own decide produced even when a later wave in the same flush
        displaced or freed the slot.
        """
        from gubernator_tpu.store.store import ItemSnapshot

        entries = list(seq)
        if not entries:
            return
        # Vectorized row extraction: one advanced-index per field over the
        # stacked (W, B) wave rows, then plain-list indexing per item —
        # per-item numpy scalar boxing dominated this loop before.
        w_arr = np.fromiter((e[1] for e in entries), np.int64, len(entries))
        l_arr = np.fromiter((e[2] for e in entries), np.int64, len(entries))
        v = {
            f: np.stack([np.asarray(getattr(r, f)) for r in rows])[
                w_arr, l_arr
            ].tolist()
            for f in self._WB_FIELDS
        }
        freed_v = np.stack([r[OUT_FREED] for r in out_rows])[
            w_arr, l_arr
        ].tolist()

        # Per-key LAST op wins, in request order: a hit followed by a
        # same-flush RESET_REMAINING must end as a remove (not resurrect
        # the pre-reset snapshot via a late batched on_change), and a
        # RESET followed by a new hit must end as the new snapshot.
        ops: Dict[str, Optional[ItemSnapshot]] = {}
        skipped = []
        for i, (key, w, lane, hi, lo) in enumerate(entries):
            # Only a token-bucket RESET_REMAINING free deletes the
            # persisted entry (reference algorithms.go:78-90); the
            # reference keeps Store entries across cache eviction and
            # restores them via Store.Get on the next cache miss.
            if freed_v[i]:
                ops[key] = None
                continue
            if not v["used"][i] or v["key_hi"][i] != hi or v["key_lo"][i] != lo:
                # The gathered row is not the one this lane's decide
                # wrote: nothing is known of the bucket, so the
                # persisted entry is left as it is, and the lane is
                # counted (gubernator_store_rows_skipped: 0 wherever
                # the gather reads the decide's own slots).
                skipped.append(i)
                continue
            ops[key] = ItemSnapshot(
                key=key,
                algorithm=v["algo"][i],
                status=v["status"][i],
                limit=v["limit"][i],
                duration=v["duration"][i],
                remaining=v["remaining"][i],
                stamp=v["stamp"][i],
                expire_at=v["expire_at"][i],
                invalid_at=v["invalid_at"][i],
                burst=v["burst"][i],
            )
        if skipped:
            self._note_skipped_rows(entries, skipped, v)
        changes = [s for s in ops.values() if s is not None]
        # Store failures here must NEVER propagate: write-behind runs
        # AFTER the table commit, and the columnar edge's caller treats a
        # check_columns exception as "safe to retry via the object path"
        # — re-applying every already-committed hit. The reference's
        # Store.OnChange has no error return either (store.go:49-65);
        # durability degrades, serving does not.
        try:
            removes = 0
            for key, s in ops.items():
                if s is None:
                    self.store.remove(key)
                    removes += 1
            if removes:
                self.metrics.store_removes.inc(removes)
            if changes:
                self.store.on_change(changes)
                self.metrics.store_on_change_items.inc(len(changes))
        except Exception:
            import logging

            logging.getLogger(__name__).exception(
                "store write-behind failed (%d changes dropped)", len(changes)
            )

    def _note_skipped_rows(self, entries, skipped, v) -> None:
        """Lanes of a flush whose acknowledged change did not reach the
        Store because their gathered row was unused or held another key:
        counted, and named in one log line a minute at most."""
        self.metrics.store_rows_skipped.inc(len(skipped))
        now = time.monotonic()
        if now - self._skipped_logged < 60.0:
            return
        self._skipped_logged = now
        import logging

        i = skipped[0]
        key, w, lane, hi, lo = entries[i]
        logging.getLogger(__name__).warning(
            "store write-behind skipped %d of %d changes of a flush: wave "
            "%d lane %d decided key %r (%d, %d) but its gathered row "
            "holds used=%s (%d, %d); the Store keeps its old entry",
            len(skipped), len(entries), w, lane, key, hi, lo,
            v["used"][i], v["key_hi"][i], v["key_lo"][i],
        )

    def _maybe_prune_key_strings(self) -> None:
        """Bound host memory: under key churn the hash->string dict keeps
        entries for keys long evicted from the device table. When it
        exceeds 2x the slot count, rebuild it from the table's live keys
        (one device readback). Dropped strings only cost an extra store
        read-through if the key returns; Loader snapshots stay complete
        because live entries always retain their strings."""
        n = self.cfg.num_groups * self.cfg.ways
        if len(self._key_strings) <= max(2 * n, 4096):
            return
        with self._lock, self.topo.dispatch_guard(), _transfer.account(
            self.metrics, "d2h", "census"
        ) as tx:
            used = np.asarray(self.table.used)  # guberlint: allow-raw-table-index -- prune wants the PHYSICAL resident set; demoted keys join via host_live_keys below
            hi = np.asarray(self.table.key_hi)[used]  # guberlint: allow-raw-table-index -- same physical scan as line above
            lo = np.asarray(self.table.key_lo)[used]  # guberlint: allow-raw-table-index -- same physical scan as line above
            tx.add((used, hi, lo))
        live = set(zip(hi.tolist(), lo.tolist()))
        if self._pager is not None:
            # Demoted keys are still live — their pages promote back
            # verbatim and Loader snapshots must stay routable — so the
            # host tier's keys survive the prune too.
            with self._lock:
                live |= self._pager.host_live_keys()
        with self._keys_lock:
            self._key_strings = {
                k: v for k, v in self._key_strings.items() if k in live
            }

    @raceguard.holds_lock("engine.table")
    def _recover_table_locked(self) -> bool:
        """Called with the lock held after a failed device call: if the
        donated table buffers were consumed — or the table points at an
        array poisoned by a failed ASYNC dispatch (pipelined mode: the
        error only surfaces at the completion stage's sync, after the
        table reference already advanced) — rebuild an empty table so
        subsequent requests serve instead of failing forever. Returns
        True when the table was rebuilt (all counters lost — a fallback
        replay is then safe, not a double-apply)."""
        try:
            deleted = getattr(self.table.key_hi, "is_deleted", lambda: False)()
            if not deleted:
                # Error-path-only health probe, never on the serving path:
                # a poisoned dependency chain raises its deferred error
                # here instead of on every future flush.
                jax.block_until_ready(self.table.key_hi)  # guberlint: allow-host-sync -- error-path table health probe
        except Exception:
            deleted = True
        if deleted:
            self.table = self._place(
                lambda: self.K.create(self.cfg.num_groups, self.cfg.ways)
            )
            if self._pager is not None:
                # The rebuilt paged table is empty with an unbound map;
                # the pager's mirror, frames, and host tier must match
                # (counter loss on failure covers the cold tier too —
                # stale host pages promoted into a fresh table would
                # resurrect pre-failure state for SOME keys only).
                self._pager.reset()
            with self._keys_lock:
                self._key_strings.clear()
        rt = self._rtier
        if rt is not None:
            # Replica tier: same consumed-or-poisoned probe on its
            # donated state; rebuild empty on damage (counter loss on
            # failure matches the accepted semantics).
            try:
                r_deleted = getattr(
                    rt.state.pending, "is_deleted", lambda: False
                )()
                if not r_deleted:
                    jax.block_until_ready(rt.state.pending)  # guberlint: allow-host-sync -- error-path replica health probe
            except Exception:
                r_deleted = True
            if r_deleted:
                rt.state = rt.recreate_state()
                deleted = True
        return deleted

    def _recover_after_failure(self) -> bool:
        """Completion-stage recovery entry (EngineBase._ticket_failed):
        same rebuild-once semantics as the dispatch path, taken under
        the device lock."""
        with self._lock:
            return self._recover_table_locked()

    # ---- direct state injection (AddCacheItem analog) ----------------------

    def inject_globals(self, globals_: Sequence) -> None:
        """Overwrite local state with authoritative GLOBAL updates from the
        owner (reference gubernator.go:425-459: rebuilds a CacheItem with
        stamp=now, expire=status.reset_time, leaky burst=limit)."""
        from gubernator_tpu.api.types import Algorithm
        from gubernator_tpu.models.bucket import FIXED_SHIFT
        from gubernator_tpu.store.store import ItemSnapshot

        now = self.now_fn()
        snaps = []
        for g in globals_:
            leaky = int(g.algorithm) == int(Algorithm.LEAKY_BUCKET)
            snaps.append(
                ItemSnapshot(
                    key=g.key,
                    algorithm=int(g.algorithm),
                    status=int(g.status.status),
                    limit=g.status.limit,
                    duration=g.duration,
                    remaining=(
                        g.status.remaining << FIXED_SHIFT
                        if leaky
                        else g.status.remaining
                    ),
                    stamp=now,
                    expire_at=g.status.reset_time,
                    burst=g.status.limit if leaky else 0,
                )
            )
        self.inject_snapshots(snaps)

    def inject_snapshots(self, items: Sequence) -> None:
        """Write raw per-key state rows into the table (Loader restore and
        Store read-through feed; reference workers.go:537-580)."""
        from gubernator_tpu.ops.inject import InjectBatch

        if not items:
            return
        now = self.now_fn()
        cfg = self.cfg

        asm = _WaveAssembler(InjectBatch.zeros, cfg.batch_size)
        new_strings: Dict[Tuple[int, int], str] = {}
        for s in items:
            hi, lo = key_hash128(s.key)
            if cfg.keep_key_strings:
                new_strings[(hi, lo)] = s.key
            grp = group_of(lo, cfg.num_groups)
            ib, w, lane = asm.place(grp)
            ib.key_hi[lane] = hi
            ib.key_lo[lane] = lo
            ib.group[lane] = grp
            ib.algo[lane] = int(s.algorithm)
            ib.status[lane] = int(s.status)
            ib.limit[lane] = s.limit
            ib.duration[lane] = s.duration
            ib.remaining[lane] = s.remaining
            ib.stamp[lane] = s.stamp
            ib.expire_at[lane] = s.expire_at
            ib.invalid_at[lane] = getattr(s, "invalid_at", 0)
            ib.burst[lane] = s.burst
            ib.active[lane] = True
            asm.commit(w, grp)

        with self._keys_lock:
            self._key_strings.update(new_strings)

        with self._lock, self.topo.dispatch_guard():
            table = self.table
            with _transfer.account(self.metrics, "h2d", "inject") as tx:
                for ib in asm.waves:
                    if self._pager is not None:
                        table = self._pager.ensure_resident(
                            table,
                            self._pager.touched_pages(ib.group, ib.active),
                        )
                    table, _ehi, _elo = self.K.inject(
                        table, ib, now, cfg.ways
                    )
                    tx.add(ib)
            self.table = table

    # ---- snapshot / restore (Loader seam, task: store) ---------------------

    def snapshot(self) -> dict:
        """Device -> host snapshot of the table (the Loader.Save analog,
        reference store.go:76-78; SURVEY.md §5 checkpoint/resume).

        Paged mode: the snapshot is the LOGICAL wide image — resident
        pages are extracted positionally into their logical offsets and
        host-tier pages are copied in place — so Loader files are
        identical to (and interchangeable with) an all-resident or flat
        table's snapshot of the same keys."""
        if self._pager is not None:
            return self._snapshot_paged()
        with self._lock, self.topo.dispatch_guard():
            tbl = self.K.to_wide(self.table)  # canonical wide snapshot
            with _transfer.account(self.metrics, "d2h", "snapshot") as tx:
                host = {f: np.asarray(getattr(tbl, f)) for f in tbl._fields}
                tx.add(host)
            self._snapshot_staging_bytes = tx.bytes
        with self._keys_lock:
            host["key_strings"] = dict(self._key_strings)
        return host

    def _snapshot_paged(self) -> dict:
        from gubernator_tpu.runtime.pager import wide_zeros

        cfg = self.cfg
        PK = self.K
        ps = PK.page_slots
        n_logical = cfg.num_groups * cfg.ways
        host = wide_zeros(PK.num_logical_pages * ps)
        with self._lock, self.topo.dispatch_guard():
            pager = self._pager
            with _transfer.account(self.metrics, "d2h", "snapshot") as tx:
                for lp in np.nonzero(pager.page_map >= 0)[0].tolist():
                    rows = PK.extract_page(
                        self.table, np.int32(int(pager.page_map[lp]))  # guberlint: allow-host-sync -- page_map is the pager's host numpy mirror
                    )
                    for f in SlotTable._fields:
                        # guberlint: allow-host-sync -- snapshot assembly: accounted page-at-a-time d2h
                        host[f][lp * ps:(lp + 1) * ps] = np.asarray(
                            getattr(rows, f)
                        )
                    tx.add(ps * PK.bytes_per_slot)
            for lp, rows in pager.host_tier.items():
                for f in SlotTable._fields:
                    host[f][lp * ps:(lp + 1) * ps] = rows[f]
            self._snapshot_staging_bytes = sum(
                a.nbytes for a in host.values()
            )
        # Trim the tail-page padding back to the logical slot count.
        host = {f: a[:n_logical] for f, a in host.items()}
        with self._keys_lock:
            host["key_strings"] = dict(self._key_strings)
        return host

    def restore(self, snap: dict) -> None:
        """Host -> device restore (the Loader.Load analog).

        Replaces the table AND the host key-string dictionary under their
        locks (the pump/executor threads read both); invalidation state
        lives in the table's own invalid_at column, which the per-wave
        read-through probe consults directly.

        Paged mode: pages with live rows fill the resident frames first
        (in logical order); the overflow restores into the host tier —
        no data is dropped even when the image holds more live pages
        than the resident budget."""
        if self._pager is not None:
            self._restore_paged(snap)
            return
        with _transfer.account(self.metrics, "h2d", "snapshot") as tx:
            fields = self._place(
                lambda: {
                    f: jax.numpy.asarray(snap[f]) for f in SlotTable._fields
                }
            )
            tx.add(fields)
        self._snapshot_staging_bytes = tx.bytes
        with self._lock, self.topo.dispatch_guard():
            self.table = self.K.from_wide(SlotTable(**fields))
        with self._keys_lock:
            self._key_strings = dict(snap.get("key_strings", {}))

    def _restore_paged(self, snap: dict) -> None:
        from gubernator_tpu.runtime.pager import wide_zeros

        PK = self.K
        ps = PK.page_slots
        fields = {f: np.asarray(snap[f]) for f in SlotTable._fields}  # guberlint: allow-host-sync -- snap is the Loader's host-side image, not device data
        n = fields["used"].shape[0]
        with self._lock, self.topo.dispatch_guard():
            self.table = self._place(PK.create)
            self._pager.reset()
            pager = self._pager
            with _transfer.account(self.metrics, "h2d", "snapshot") as tx:
                for lp in range(PK.num_logical_pages):
                    lo, hi = lp * ps, min((lp + 1) * ps, n)
                    if lo >= n or not fields["used"][lo:hi].any():
                        continue
                    page = wide_zeros(ps)
                    for f in SlotTable._fields:
                        page[f][: hi - lo] = fields[f][lo:hi]
                    # acquire_frame is the single bind gate: on a mesh
                    # it draws from the page's own shard pool, so the
                    # restore preserves per-shard placement invariants.
                    pp = pager.acquire_frame(lp)
                    if pp is not None:
                        self.table = PK.write_page(
                            self.table, np.int32(lp), np.int32(pp),
                            SlotTable(**page),
                        )
                        pager.page_map[lp] = pp
                        tx.add(page)
                    else:
                        pager.host_tier[lp] = page
            self._snapshot_staging_bytes = tx.bytes
        with self._keys_lock:
            self._key_strings = dict(snap.get("key_strings", {}))


class DeviceEngine(MeshEngine):
    """MeshEngine at mesh shape ``(1,)`` — the single-chip engine name
    that V1Service, the daemon, and the test suites construct. The
    default topology (SingleChipTopology) IS the pre-unification
    DeviceEngine binding, so this shell only preserves the public type
    name; every behavior lives in the core."""


def _assemble_column_waves(
    cols, hi, lo, grp, now, batch_size: int, max_waves: int,
    width_candidates=(), stacked_widths=(), group=None, home=None,
):
    """Vectorized wave assembly shared by the engines' columnar paths:
    wave = occurrence rank within the group (stable sort keeps arrival
    order, preserving per-key sequencing); lane = arrival rank within
    the wave. `grp` is what two lanes of one wave may not share; `group`
    is the operand's group column where that differs (the replica tier:
    `grp` is the (home, slot) pair, `group` the slot) and `home` its
    home row. Returns (waves, wave, lane, ix, W, B): `waves` the W
    waves' WaveOperands in order, views of one buffer a width, and B the
    first wave's width; or None when a wave would hold more than
    batch_size lanes (the caller falls back to the object path).

    The wave count is not bounded: a key that comes more often than
    `max_waves` times makes more waves than one launch holds, and
    _upload cuts them into consecutive launches of one flush.

    `width_candidates` optionally narrows the device batch width to the
    actual occupancy — the kernel's cost is per-LANE — using only
    already-compiled widths. A call of several waves narrows only to a
    width among `stacked_widths`, those whose stacked launch is warm: a
    wider wave costs microseconds of device time, a launch a wave costs
    a millisecond each under the engine lock. So an assembly that one
    launch holds (W <= max_waves) runs at one width, its first wave's.
    One that needs several launches anyway gives every wave the
    narrowest such width that holds it: wave w holds the groups that
    come more than w times, so its waves never widen, and the long tail
    that one hot key makes is uploaded and read back at the narrowest
    width, not at its first wave's."""
    from gubernator_tpu.models.bucket import MAX_COUNT, MAX_DURATION_MS

    n = cols.n
    order = np.argsort(grp, kind="stable")
    sg = grp[order]
    wave_sorted = np.arange(n) - np.searchsorted(sg, sg, side="left")
    wave = np.empty(n, np.int64)
    wave[order] = wave_sorted
    W = int(wave.max()) + 1
    fill = np.bincount(wave)  # lanes a wave: never more than the wave before
    if fill[0] > batch_size:
        return None
    order2 = np.argsort(wave, kind="stable")
    sw = wave[order2]
    lane = np.empty(n, np.int64)
    lane[order2] = np.arange(n) - np.searchsorted(sw, sw, side="left")

    fits = sorted(  # of an immutable snapshot; the warmer swaps atomically
        s for s in width_candidates
        if s < batch_size and (W == 1 or s in stacked_widths)
    ) + [batch_size]
    if W <= max_waves:
        fill[:] = fill[0]  # one launch holds it: one width, its first wave's
    widths = np.array(fits)[np.searchsorted(fits, fill)]
    firsts = [0, *(np.nonzero(np.diff(widths))[0] + 1).tolist(), W]
    widths = widths.tolist()

    # Encode columns (the encode_one clamps, vectorized).
    hits = np.clip(cols.hits, -MAX_COUNT, MAX_COUNT)
    limit = np.clip(cols.limit, -MAX_COUNT, MAX_COUNT)
    duration = np.clip(cols.duration, 0, MAX_DURATION_MS)
    burst = np.clip(cols.burst, 0, MAX_COUNT)
    is_leaky = cols.algo.astype(np.int64) == 1
    burst = np.where(is_leaky & (burst == 0), limit, burst)
    # created_at==0 counts as absent, like the object path (server.py
    # treats 0 the same as unset before handing to the engine).
    created = np.where(
        cols.has_created.astype(bool) & (cols.created_at != 0),
        cols.created_at,
        np.int64(now),
    )
    fields = dict(
        key_hi=hi, key_lo=lo, group=grp if group is None else group,
        algo=cols.algo.astype(np.int8),
        behavior=cols.behavior.astype(np.int32),
        hits=hits, limit=limit, duration=duration, rate_num=duration,
        eff_duration=duration, burst=burst, created_at=created,
    )

    waves = []
    for first, end in zip(firsts, firsts[1:]):
        wo = WaveOperand.zeros(widths[first], end - first)
        if end - first == W:
            at, own = (wave, lane), slice(None)
        else:
            own = (wave >= first) & (wave < end)
            at = (wave[own] - first, lane[own])
        wb = wo.batch
        for f, col in fields.items():
            getattr(wb, f)[at] = col[own]
        wb.active[at] = True
        if home is not None:
            wo.home[at] = home[own]
        waves += [wo.wave(w) for w in range(end - first)]
    return waves, wave, lane, (wave, lane), W, waves[0].lanes


def _demux_lanes(out_rows, ix):
    """(status, limit, remaining, reset_time) in request order from the
    waves' output rows (_read_waves), whatever each wave's width: `ix`
    is each request's (wave, lane) — the demux shared by the engines'
    columnar paths."""
    wave, lane = ix
    widths = np.fromiter(
        (r.shape[1] for r in out_rows), np.int64, len(out_rows)
    )
    at = (np.cumsum(widths) - widths)[wave] + lane
    lanes = np.concatenate(out_rows, axis=1)  # (R, every wave's lanes)
    return tuple(
        lanes[r, at]
        for r in (OUT_STATUS, OUT_LIMIT, OUT_REMAINING, OUT_RESET_TIME)
    )


def _note_hotkeys_columnar(hk, hi, lo, hits, status) -> None:
    """Aggregate one columnar flush into the hot-key sketch. Keyed by
    the 128-bit hash pair — the columnar edge never decodes key strings
    for this (cost discipline); display names resolve lazily at
    snapshot/render time through the sketch's resolver or the object
    path's updates. All inputs are already-materialized host arrays."""
    agg: Dict[Tuple[int, int], list] = {}
    for h, l, w, s in zip(
        hi.tolist(), lo.tolist(), hits.tolist(), status.tolist()
    ):
        o = 1 if s == 1 else 0  # api.types.Status.OVER_LIMIT
        k = (h, l)
        ent = agg.get(k)
        if ent is None:
            agg[k] = [max(int(w), 0), o]
        else:
            ent[0] += max(int(w), 0)
            ent[1] += o
    if agg:
        hk.update([(k, v[0], v[1], None) for k, v in agg.items()])


def _merge_joined(batch):
    """The columns of one merged flush: the members' columns in arrival
    order (wire.concat_columns), every lane carrying a created_at: its
    item's own, else its member's `now` (the clock at the call's
    arrival, or what its caller passed: a GLOBAL call's local decide
    and its replicated legs share one stamp, service/fastpath.py). The
    pump does the same for the calls it coalesces (check_bulk stamps
    created_at at the call's arrival). The members' own columns are
    left as they were: a member the batch cannot serve is still served
    from them."""
    from gubernator_tpu import wire as _wire

    cols = _wire.concat_columns([m.cols for m in batch])
    nows = np.repeat(
        np.array([m.now for m in batch], np.int64),
        [m.cols.n for m in batch],
    )
    carried = cols.has_created.astype(bool) & (cols.created_at != 0)
    cols.created_at = np.where(carried, cols.created_at, nows)
    cols.has_created = np.ones(cols.n, np.uint8)
    return cols


def _select_columns(cols, select: np.ndarray):
    """Subset view of RequestColumns for check_columns(select=...): field
    arrays are fancy-indexed; key bytes are NOT re-sliced — key hashes
    are computed from the ORIGINAL columns before selection, and
    key_string() must be called on the original columns too. key_offsets
    is poisoned to None so any code path that tries to hash or slice
    keys on the subset view fails loudly (TypeError) instead of reading
    misaligned offsets."""
    from gubernator_tpu.wire import PER_ITEM_FIELDS

    return dataclasses.replace(
        cols,
        n=int(len(select)),
        key_data=cols.key_data,
        key_offsets=None,  # poisoned: unusable after select (see above)
        **{f: getattr(cols, f)[select] for f in PER_ITEM_FIELDS},
    )


class _Bulk:
    """A bulk queue entry: N (req, _Slot) pairs resolved by one Future."""

    __slots__ = ("work", "slots", "future", "t_enq")

    def __init__(self, work, slots, future):
        self.work = work
        self.slots = slots
        self.future = future
        self.t_enq = time.perf_counter()

    def resolve(self) -> None:
        if not self.future.done():
            self.future.set_result(
                [
                    s.value
                    if s.done()
                    else RateLimitResp(error=ERR_ENGINE_DRAINING)
                    for s in self.slots
                ]
            )


_FLUSH = object()
_STOP = object()


# Declared lock protocol (docs/robustness.md "Race sanitizer").
# Write-only ("w:") fields are read racily on purpose by the debug
# snapshot, the SLO sampler, and the test suites (single reference or
# int reads); the tight read+write protocol applies to the bulk/census/
# admission caches and the dirty-key registry, whose readers all take
# the matching lock (the deliberate lock-free None-gates sit inside
# racy_read escapes above).
raceguard.guarded_by(EngineBase, {
    "_bulks": "engine.bulks",
    "_census_cache": "engine.census",
    "_census_ts": "engine.census",
    "_census_prev": "engine.census",
    "_admission_cache": "engine.admission",
    "_admission_ts": "engine.admission",
    "_shard_decisions": "w:engine.shards",
    "_replica_decisions": "w:engine.shards",
    "_inflight": "w:engine.pipeline",
})
raceguard.guarded_by(MeshEngine, {
    "table": "w:engine.table",
    "_key_strings": "w:engine.keys",
    "_dirty": "engine.dirty",
})
