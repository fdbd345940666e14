"""Prometheus metrics, name-compatible with the reference catalog
(reference docs/prometheus.md:17-43).

The reference's functional tests poll these metrics as their
synchronization API (SURVEY.md §4) — sample names must match exactly
(e.g. `gubernator_broadcast_duration_count`). Two exposition notes:

- Counter-style metrics are exposed by _BareCounter below: client_python's
  Counter force-appends `_total` to the exposition name, but the
  reference's Go names (`gubernator_getratelimit_counter`,
  `gubernator_cache_access_count`, ...) have no suffix. _BareCounter keeps
  the bare Go sample name AND a correct `# TYPE <name> counter` line.
- Summary emits `<name>_count` / `<name>_sum`, matching Go's summaries.

Each Daemon owns one CollectorRegistry (like the reference's per-daemon
registry, daemon.go:91-103) so in-process cluster fixtures don't collide.
"""

from __future__ import annotations

import logging
import math
import threading
import time

from prometheus_client import (
    CollectorRegistry,
    Gauge,
    Summary,
    generate_latest,
    CONTENT_TYPE_LATEST,
)

from gubernator_tpu.utils import lockorder, raceguard

log = logging.getLogger("gubernator_tpu.metrics")


def _escape_label(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


class _BareChild:
    __slots__ = ("_parent", "_key")

    def __init__(self, parent: "_BareCounter", key: tuple):
        self._parent = parent
        self._key = key

    def inc(self, amount: float = 1) -> None:
        p = self._parent
        with p._lock:
            p._values[self._key] = p._values.get(self._key, 0.0) + amount

    def set(self, value: float) -> None:
        """Monotonic set — bridges externally-accumulated engine counters
        at scrape time."""
        p = self._parent
        with p._lock:
            p._values[self._key] = float(value)

    def get(self) -> float:
        p = self._parent
        with p._lock:
            return p._values.get(self._key, 0.0)


class _BareCounter:
    """Monotonic counter exposed under its bare Go name with a correct
    `# TYPE <name> counter` line.

    prometheus_client cannot express this (its Counter appends `_total`
    per OpenMetrics; a raw Metric('counter') mangles the TYPE header), so
    value storage and text exposition live here; Metrics.render() prepends
    these lines to the registry's standard output."""

    def __init__(self, name: str, doc: str, labelnames=()):
        self.name = name
        self.doc = doc
        self.labelnames = tuple(labelnames)
        self._values: dict = {}
        self._lock = lockorder.make_lock("metrics.counter")
        if not self.labelnames:
            self._values[()] = 0.0

    def labels(self, *values) -> _BareChild:
        if len(values) != len(self.labelnames):
            raise ValueError(
                f"{self.name}: expected {len(self.labelnames)} label values"
            )
        return _BareChild(self, tuple(str(v) for v in values))

    # unlabeled convenience (mirrors prometheus_client's API shape)
    def inc(self, amount: float = 1) -> None:
        _BareChild(self, ()).inc(amount)

    def set(self, value: float) -> None:
        _BareChild(self, ()).set(value)

    def sample_names(self) -> list:
        return [self.name]

    def render_lines(self) -> list:
        out = [f"# HELP {self.name} {self.doc}", f"# TYPE {self.name} counter"]
        with self._lock:
            items = sorted(self._values.items())
        for key, v in items:
            if key:
                lbl = ",".join(
                    f'{n}="{_escape_label(val)}"'
                    for n, val in zip(self.labelnames, key)
                )
                out.append(f"{self.name}{{{lbl}}} {v}")
            else:
                out.append(f"{self.name} {v}")
        return out


class _HistChild:
    __slots__ = ("_parent", "_key")

    def __init__(self, parent: "Log2Histogram", key: tuple):
        self._parent = parent
        self._key = key

    def observe(self, value: float, trace_id: str = "") -> None:
        self._parent._observe(self._key, value, trace_id)


class Log2Histogram:
    """Fixed-bucket power-of-two histogram, exposed as real Prometheus
    histogram series (`<name>_bucket{le=...}` / `_sum` / `_count`).

    The reference catalog only ships Summaries; histograms are what the
    device tier needs — cross-process aggregatable latency/shape
    distributions for the engine flush path (docs/monitoring.md).
    Bucket upper bounds are `scale * 2**i` for i in [0, n_buckets);
    observe() is O(1) (one frexp + one lock hold, no allocation), cheap
    enough to run per FLUSH / per sync TICK — it is never called per
    request."""

    def __init__(
        self,
        name: str,
        doc: str,
        scale: float = 1.0,
        n_buckets: int = 24,
        labelnames=(),
    ):
        self.name = name
        self.doc = doc
        self.scale = float(scale)
        self.n_buckets = int(n_buckets)
        self.labelnames = tuple(labelnames)
        self._les = [self.scale * (1 << i) for i in range(self.n_buckets)]
        self._lock = lockorder.make_lock("metrics.histogram")
        # key -> [bucket counts (n_buckets + 1, last = +Inf), sum,
        #         {bucket index -> (trace_id, value, unix_ts) exemplar}]
        # Exemplar memory is bounded: one (the latest) per bucket per
        # label set, populated only when observe() is handed a sampled
        # trace id (docs/monitoring.md "Tracing the pipeline").
        self._series: dict = {}
        if not self.labelnames:
            self._series[()] = [[0] * (self.n_buckets + 1), 0.0, {}]

    def sample_names(self) -> list:
        return [self.name, f"{self.name}_bucket",
                f"{self.name}_sum", f"{self.name}_count"]

    def labels(self, *values) -> _HistChild:
        if len(values) != len(self.labelnames):
            raise ValueError(
                f"{self.name}: expected {len(self.labelnames)} label values"
            )
        return _HistChild(self, tuple(str(v) for v in values))

    def declare(self, *values) -> _HistChild:
        """labels() that also exposes the series at 0 from now on: a
        reader that takes deltas between two scrapes gets nothing for a
        series that is absent from the first."""
        child = self.labels(*values)
        with self._lock:
            self._series.setdefault(
                child._key, [[0] * (self.n_buckets + 1), 0.0, {}]
            )
        return child

    def observe(self, value: float, trace_id: str = "") -> None:
        self._observe((), value, trace_id)

    def observe_many(self, items) -> None:
        """observe() for [(label values, float value)] under one lock
        hold: a flush hands over its stages at once."""
        rows = [(key, v, self._bucket_index(v)) for key, v in items]
        with self._lock:
            for key, v, i in rows:
                s = self._series.get(key)
                if s is None:
                    s = self._series[key] = [
                        [0] * (self.n_buckets + 1), 0.0, {}
                    ]
                s[0][i] += 1
                s[1] += v

    def _bucket_index(self, value: float) -> int:
        if value <= self.scale:
            return 0
        m, e = math.frexp(value / self.scale)  # value/scale = m * 2**e
        i = e - 1 if m == 0.5 else e  # smallest i with value <= scale*2**i
        return min(i, self.n_buckets)  # n_buckets = the +Inf bucket

    def _observe(self, key: tuple, value: float, trace_id: str = "") -> None:
        v = float(value)
        i = self._bucket_index(v)
        with self._lock:
            s = self._series.get(key)
            if s is None:
                s = self._series[key] = [[0] * (self.n_buckets + 1), 0.0, {}]
            s[0][i] += 1
            s[1] += v
            if trace_id:
                s[2][i] = (trace_id, v, time.time())

    def render_lines(self, openmetrics: bool = False) -> list:
        """Prometheus text lines; with openmetrics=True each bucket that
        holds an exemplar gets the OpenMetrics `# {trace_id="..."}`
        suffix (exemplars are an OpenMetrics-only construct — plain
        Prometheus text exposition stays byte-identical to before)."""
        out = [f"# HELP {self.name} {self.doc}",
               f"# TYPE {self.name} histogram"]
        with self._lock:
            items = sorted(
                (k, list(s[0]), s[1], dict(s[2]))
                for k, s in self._series.items()
            )
        for key, counts, total, exemplars in items:
            lbl = ",".join(
                f'{n}="{_escape_label(v)}"'
                for n, v in zip(self.labelnames, key)
            )
            sep = "," if lbl else ""
            cum = 0
            for i, (le, c) in enumerate(zip(self._les, counts)):
                cum += c
                line = f'{self.name}_bucket{{{lbl}{sep}le="{le:.12g}"}} {cum}'
                if openmetrics and i in exemplars:
                    tid, v, ts = exemplars[i]
                    line += (
                        f' # {{trace_id="{tid}"}} {v:.9g} {ts:.3f}'
                    )
                out.append(line)
            cum += counts[-1]
            inf_line = f'{self.name}_bucket{{{lbl}{sep}le="+Inf"}} {cum}'
            if openmetrics and self.n_buckets in exemplars:
                tid, v, ts = exemplars[self.n_buckets]
                inf_line += f' # {{trace_id="{tid}"}} {v:.9g} {ts:.3f}'
            out.append(inf_line)
            suffix = f"{{{lbl}}}" if lbl else ""
            out.append(f"{self.name}_sum{suffix} {total}")
            out.append(f"{self.name}_count{suffix} {cum}")
        return out

    def summary(self, qs=(0.5, 0.99)) -> dict:
        """Aggregate distribution summary across all label sets: count,
        sum, and linearly-interpolated quantiles (bench ledger rows and
        the /debug/engine snapshot)."""
        with self._lock:
            counts = [0] * (self.n_buckets + 1)
            total = 0.0
            for buckets, s, _exemplars in self._series.values():
                total += s
                for i, c in enumerate(buckets):
                    counts[i] += c
        n = sum(counts)
        out = {"count": n, "sum": total}
        if n == 0:
            for q in qs:
                out[f"p{int(q * 100)}"] = 0.0
            return out
        for q in qs:
            rank = q * n
            cum = 0
            val = float(self._les[-1] * 2)  # +Inf estimate: one octave up
            for i, c in enumerate(counts):
                if c == 0:
                    continue
                if cum + c >= rank:
                    hi = (
                        self._les[i]
                        if i < self.n_buckets
                        else self._les[-1] * 2
                    )
                    lo = 0.0 if i == 0 else self._les[i - 1]
                    val = lo + (hi - lo) * max(rank - cum, 0.0) / c
                    break
                cum += c
            out[f"p{int(q * 100)}"] = val
        return out

    def label_summaries(self, qs=(0.5, 0.99)) -> dict:
        """Per-label-set summaries: {label_values_tuple: summary_dict}.
        The bench ledger uses this to break the stage-duration histogram
        out per stage instead of blending all stages into one blob."""
        with self._lock:
            keys = list(self._series)
        out = {}
        for key in keys:
            # Reuse summary()'s interpolation over a single series by
            # projecting through a temporary view of the counts.
            with self._lock:
                s = self._series.get(key)
                if s is None:
                    continue
                counts = list(s[0])
                total = s[1]
            n = sum(counts)
            summ = {"count": n, "sum": total}
            for q in qs:
                summ[f"p{int(q * 100)}"] = self._quantile(counts, n, q)
            out[key] = summ
        return out

    def _quantile(self, counts, n, q) -> float:
        if n == 0:
            return 0.0
        rank = q * n
        cum = 0
        val = float(self._les[-1] * 2)
        for i, c in enumerate(counts):
            if c == 0:
                continue
            if cum + c >= rank:
                hi = self._les[i] if i < self.n_buckets else self._les[-1] * 2
                lo = 0.0 if i == 0 else self._les[i - 1]
                val = lo + (hi - lo) * max(rank - cum, 0.0) / c
                break
            cum += c
        return val


# Log2-ms bin count of the table census (ops/census.py CENSUS_BUCKETS;
# mirrored literally so this module stays jax-free — the census module
# imports jax, and catalog_names() must import without it).
CENSUS_BUCKETS = 32


class CensusSnapshotHistogram:
    """Table-census age/idle distribution as Prometheus histogram series.

    Unlike Log2Histogram this is a SNAPSHOT, not an event stream: each
    census publishes the full per-bin slot counts (how many resident
    slots currently have age/idle in [2^(i-1), 2^i) ms), and render
    replaces — never accumulates — the series. `le` bounds are seconds
    (0.001 * 2**i); the last census bin is the +Inf bucket; `_count` is
    the live slot population and `_sum` the total age/idle seconds.
    Registered through Metrics.register_renderable like the engine's
    Log2Histograms, fed by engine_sync from the TTL-cached census."""

    def __init__(self, name: str, doc: str):
        self.name = name
        self.doc = doc
        self._lock = lockorder.make_lock("metrics.census")
        self._hist_ms: list = [0] * CENSUS_BUCKETS
        self._sum_ms = 0

    def sample_names(self) -> list:
        return [self.name, f"{self.name}_bucket",
                f"{self.name}_sum", f"{self.name}_count"]

    def update(self, hist_ms, sum_ms) -> None:
        with self._lock:
            self._hist_ms = list(hist_ms)
            self._sum_ms = int(sum_ms)

    def render_lines(self, openmetrics: bool = False) -> list:
        with self._lock:
            counts = list(self._hist_ms)
            total_s = self._sum_ms / 1000.0
        out = [f"# HELP {self.name} {self.doc}",
               f"# TYPE {self.name} histogram"]
        cum = 0
        for i, c in enumerate(counts[:-1]):
            cum += c
            le = 0.001 * (1 << i)
            out.append(f'{self.name}_bucket{{le="{le:.12g}"}} {cum}')
        cum += counts[-1] if counts else 0
        out.append(f'{self.name}_bucket{{le="+Inf"}} {cum}')
        out.append(f"{self.name}_sum {total_s}")
        out.append(f"{self.name}_count {cum}")
        return out


# Log2-hit bin count of the admission scan (ops/admission.py
# ADMISSION_BUCKETS; mirrored literally for the same jax-free reason).
ADMISSION_BUCKETS = 32


class AdmissionExcessHistogram:
    """Per-window admission-excess distribution as Prometheus histogram
    series. Same snapshot-replace contract as CensusSnapshotHistogram,
    but the `le` bounds are HITS (2**i), not seconds: bucket i counts
    resident keys whose hits-admitted-beyond-limit falls in
    [2^(i-1), 2^i); `_count` is the excess-key population and `_sum`
    the total excess hits. Fed from the TTL-cached admission snapshot
    by engine_sync — a scrape never runs device work."""

    def __init__(self, name: str, doc: str):
        self.name = name
        self.doc = doc
        self._lock = lockorder.make_lock("metrics.admission")
        self._hist: list = [0] * ADMISSION_BUCKETS
        self._sum_hits = 0

    def sample_names(self) -> list:
        return [self.name, f"{self.name}_bucket",
                f"{self.name}_sum", f"{self.name}_count"]

    def update(self, hist, sum_hits) -> None:
        with self._lock:
            self._hist = list(hist)
            self._sum_hits = int(sum_hits)

    def render_lines(self, openmetrics: bool = False) -> list:
        with self._lock:
            counts = list(self._hist)
            total = self._sum_hits
        out = [f"# HELP {self.name} {self.doc}",
               f"# TYPE {self.name} histogram"]
        cum = 0
        for i, c in enumerate(counts[:-1]):
            cum += c
            out.append(f'{self.name}_bucket{{le="{1 << i}"}} {cum}')
        cum += counts[-1] if counts else 0
        out.append(f'{self.name}_bucket{{le="+Inf"}} {cum}')
        out.append(f"{self.name}_sum {total}")
        out.append(f"{self.name}_count {cum}")
        return out


class HotKeySketch:
    """Top-K hot-key attribution via a weighted space-saving (Misra-
    Gries) sketch: at most `k` tracked keys, each entry carrying its
    estimated hit count, the over-estimate bound `err` inherited at
    insertion, and an over-limit tally. Guarantees (classic space-
    saving): every key with true weight > total/k is tracked, and each
    entry's estimate overshoots its true weight by at most its `err`
    (<= total/k) — property-tested against an exact counter in
    tests/test_observability.py.

    Updated at the flush boundary where keys are already on host (the
    engine object path's placements and the columnar edge's hash
    columns); keyed by the 128-bit key hash pair so the columnar path
    never has to decode key strings, with display names attached
    opportunistically (object-path requests carry them) and bounded to
    the tracked set. k=0 disables the sketch entirely — update() is one
    attribute read, no allocation."""

    def __init__(self, name: str, doc: str, k: int = 128):
        self.name = name
        self.doc = doc
        self._lock = lockorder.make_lock("metrics.hotkeys")
        self._k = int(k)
        # (hi, lo) -> [count, err, over_limit]
        self._entries: dict = {}
        self._names: dict = {}  # (hi, lo) -> display string (tracked only)
        self._total = 0
        self._resolver = None  # fallback (hi, lo) -> Optional[str]

    @property
    def k(self) -> int:
        return self._k

    def configure(self, k: int) -> None:
        with self._lock:
            self._k = int(k)
            if self._k <= 0:
                self._entries.clear()
                self._names.clear()

    def set_resolver(self, fn) -> None:
        """Fallback display-name resolver ((hi, lo) -> str or None),
        e.g. DeviceEngine.key_string — used at snapshot/render time for
        keys whose strings never crossed an update()."""
        self._resolver = fn

    def update(self, rows) -> None:
        """Apply one flush's aggregated per-key rows:
        [(hi, lo), weight, over_limit_count, name-or-None]. Caller
        pre-aggregates per flush so the O(k) eviction scan runs per
        distinct new key, not per request."""
        if self._k <= 0:
            return
        with self._lock:
            e = self._entries
            k = self._k
            names = self._names
            for key, w, over, name in rows:
                if w <= 0 and not over:
                    continue
                w = max(int(w), 0)
                self._total += w
                ent = e.get(key)
                if ent is not None:
                    ent[0] += w
                    ent[2] += over
                elif len(e) < k:
                    e[key] = [w, 0, over]
                else:
                    # Space-saving eviction: the minimum-count entry is
                    # replaced; the newcomer inherits its count as err.
                    victim = min(e, key=lambda kk: e[kk][0])
                    floor = e[victim][0]
                    del e[victim]
                    names.pop(victim, None)
                    e[key] = [floor + w, floor, over]
                if name is not None and key not in names:
                    names[key] = name

    def _display(self, key, names) -> str:
        """Display name from a names SNAPSHOT (never the live dict: the
        resolver may take the engine key lock, which the flush path
        acquires BEFORE metrics.hotkeys — resolving under our lock
        would invert that order)."""
        name = names.get(key)
        if name is None and self._resolver is not None:
            try:
                name = self._resolver(key[0], key[1])
            except Exception:
                name = None
        return name if name is not None else f"hash:{key[0]:x}:{key[1]:x}"

    def _sorted_copy(self) -> tuple:
        """(entries, names) copied under the lock: entry VALUE lists are
        copied too, so a concurrent update() (or one re-entered through
        the display resolver) can't mutate the rows a snapshot already
        sorted — pre-fix, a /debug/hotkeys row could report more hits
        than the payload's own total_hits."""
        entries = sorted(
            ((key, list(ent)) for key, ent in self._entries.items()),
            key=lambda kv: -kv[1][0],
        )
        return entries, dict(self._names)

    def snapshot(self) -> dict:
        """JSON payload for /debug/hotkeys: entries sorted hottest-
        first, with the sketch's global error bound (total/k)."""
        with self._lock:
            entries, names = self._sorted_copy()
            total = self._total
            k = self._k
        return {
            "k": k,
            "total_hits": total,
            "max_error": (total // k) if k else 0,
            "entries": [
                {
                    "key": self._display(key, names),
                    "key_hash": [key[0], key[1]],
                    "hits": ent[0],
                    "err": ent[1],
                    "over_limit": ent[2],
                }
                for key, ent in entries
            ],
        }

    # -- renderable protocol (Metrics.register_renderable) -------------------

    def sample_names(self) -> list:
        return [self.name]

    def render_lines(self, openmetrics: bool = False) -> list:
        """Top-K gauge series, one per tracked key — cardinality is
        bounded by k by construction (and counts can fall on eviction,
        hence gauge, not counter)."""
        out = [f"# HELP {self.name} {self.doc}",
               f"# TYPE {self.name} gauge"]
        with self._lock:
            entries, names = self._sorted_copy()
        for key, ent in entries:
            out.append(
                f'{self.name}'
                f'{{key="{_escape_label(self._display(key, names))}"}} '
                f"{ent[0]}"
            )
        return out

    def summary(self) -> dict:
        """Debug-snapshot shape (the /debug/engine histogram map calls
        summary() on every engine renderable)."""
        with self._lock:
            return {
                "count": len(self._entries),
                "k": self._k,
                "total_hits": self._total,
            }


# Declared lock protocol (docs/robustness.md "Race sanitizer"). _k is
# write-guarded only: update()'s disabled-sketch precheck and the k
# property read it racily on purpose (int read, configure() is rare).
raceguard.guarded_by(HotKeySketch, {
    "_entries": "metrics.hotkeys",
    "_names": "metrics.hotkeys",
    "_total": "metrics.hotkeys",
    "_k": "w:metrics.hotkeys",
    "_resolver": "@thread",
})


# The device-tier histogram families (single source of truth: the engine
# tier instantiates exactly these via EngineMetrics, Metrics exposes them
# through register_renderable, and tools/check_metrics_names.py audits
# the names against docs/monitoring.md without importing jax).
# The flush stages tracing.stage() times (docs/monitoring.md "Tracing
# the pipeline"), and every `stage` value of the engine's histogram.
FLUSH_STAGES = (
    "hash", "waves", "keydict", "lock_wait", "dispatch", "readback", "post",
    # a columnar call's wait in the group commit at check_columns' entry:
    # until it leads the waiting batch, or until that batch's leader has
    # its answer (observed once a call that joined, never by a lone one)
    "join",
    # the Store's sequence (docs/persistence.md), observed only with a
    # Store attached: the first two a wave, inside dispatch; the third a
    # flush, inside post (the pump: inside resolve)
    "readthrough", "store_rows", "write_behind",
)
ENGINE_STAGES = (
    "intake", "assemble", "inflight_wait", "device_sync", "resolve",
) + FLUSH_STAGES

# The stages of one GetRateLimits call by the path that served it
# (docs/monitoring.md "Tracing the pipeline"): they partition the
# handler's time. PeersV1 calls observe the same stages under
# path="peer_columnar" / "peer_object".
CALL_STAGES = {
    "columnar": ("executor_wait", "parse", "engine", "build", "loop_return"),
    "mixed": ("executor_wait", "parse", "engine", "build", "loop_return",
              "route", "engine_wait"),
    "object": ("columnar_attempt", "pb_decode", "route", "engine_wait",
               "pb_encode"),
}
CALL_STAGES["peer_columnar"] = CALL_STAGES["columnar"]
CALL_STAGES["peer_object"] = CALL_STAGES["object"]
# Why a call left the columnar path: edge_calls{path,reason}.
EDGE_REASONS = {
    "columnar": ("",),
    "mixed": ("ring", "gregorian"),
    "object": ("waves", "slow_item", "gregorian", "ring", "forward_only",
               "disabled", "error"),
}
EDGE_REASONS["peer_columnar"] = EDGE_REASONS["columnar"]
EDGE_REASONS["peer_object"] = EDGE_REASONS["object"]


def engine_wave_transfers() -> _BareCounter:
    """The engine-owned counter of the crossings of the host-device
    boundary that serving waves make. The engine adds to it where it
    observes gubernator_engine_flush_waves, and wire_engine_telemetry
    exposes it on the very next lines, so a scrape reads the two as one:
    their ratio is the arrays a wave costs each way."""
    c = _BareCounter(
        "gubernator_engine_wave_transfers",
        "Arrays that crossed the host-device boundary for serving "
        "waves: operands uploaded (h2d) and outputs read (d2h). One of "
        "each a launch (a wave, or a run of waves stacked); over "
        "gubernator_engine_flush_waves_sum it is the arrays a wave "
        "costs each way. It never counted what else a Store's sequence "
        "moves under the lock: gubernator_engine_store_wave_crossings "
        "does.",
        ["direction"],
    )
    for direction in ("h2d", "d2h"):
        c.labels(direction).inc(0)
    return c


# The device programs of the Store's per-wave sequence, in its order.
STORE_WAVE_PROGRAMS = ("probe", "inject", "decide", "gather_rows")


# How the waves of a flush with a Store ran under the engine lock.
STORE_SEQUENCES = ("stacked", "per_wave")


def engine_wave_programs() -> _BareCounter:
    """The engine-owned counter of the device programs the Store's
    per-wave sequence launches, added where the engine observes
    gubernator_engine_flush_waves and exposed beside it (after
    gubernator_engine_flush_launches): over
    gubernator_engine_flush_waves_sum it is the programs one wave costs
    with a Store attached."""
    c = _BareCounter(
        "gubernator_engine_wave_programs",
        "Device programs launched under the engine lock by the waves of "
        "flushes that ran the Store's per-wave sequence, by program: "
        "probe (residency of the wave's keys), inject (Store rows read "
        "through, only for a wave with a miss the Store answered), "
        "decide, gather_rows (the rows the write-behind persists). 0 "
        "without a Store.",
        ["program"],
    )
    for program in STORE_WAVE_PROGRAMS:
        c.labels(program).inc(0)
    return c


def engine_store_wave_crossings() -> _BareCounter:
    """The engine-owned counter of the arrays the Store's per-wave
    sequence moves across the host-device boundary while it holds the
    engine lock, added where the engine observes
    gubernator_engine_flush_waves and exposed after
    gubernator_engine_wave_programs: over
    gubernator_engine_flush_waves_sum it is the crossings one wave
    makes under the lock with a Store attached."""
    c = _BareCounter(
        "gubernator_engine_store_wave_crossings",
        "Arrays that crossed the host-device boundary under the engine "
        "lock in the Store's per-wave sequence, by direction: d2h the "
        "probe's answer (one a wave), the two key columns an inject "
        "displaced, and an earlier wave's packed rows where a key was "
        "displaced between its own waves; h2d the fields of an inject's "
        "operand (only a wave with a miss the Store answered uploads "
        "anything: the probe and the row gather read what is on the "
        "device). The wave's output vector and its packed rows are "
        "read after the lock is released, and the operand is uploaded "
        "before it: both are gubernator_engine_wave_transfers'. 0 "
        "without a Store.",
        ["direction"],
    )
    for direction in ("h2d", "d2h"):
        c.labels(direction).inc(0)
    return c


def engine_flushes_over_max_waves() -> _BareCounter:
    """The engine-owned counter of columnar flushes whose assembly made
    more waves than one launch holds (max_waves): one key more often
    than that in one call. Exposed after
    gubernator_engine_store_wave_crossings."""
    return _BareCounter(
        "gubernator_engine_flushes_over_max_waves",
        "Columnar flushes that ran more than max_waves waves, as "
        "consecutive launches under one hold of the engine lock: a "
        "call in which one key (or one slot group) comes more often "
        "than max_waves times. Such a call used to leave the columnar "
        "path and count under gubernator_edge_calls' reason waves.",
    )


def engine_store_counters() -> dict:
    """The engine-owned counters of what it asks of an attached Store
    (reference store.go:49-65), keyed as EngineMetrics holds them."""
    gets = _BareCounter(
        "gubernator_store_gets",
        "Store.get calls the engine made for a key its table did not "
        "hold (never seen by this process, or evicted), by result: hit "
        "(the Store held it; the row is injected before the wave's "
        "decide) or miss (also a Store that raised).",
        ["result"],
    )
    for result in ("hit", "miss"):
        gets.labels(result).inc(0)
    flushes = _BareCounter(
        "gubernator_engine_store_flushes",
        "Flushes that ran with a Store attached, by the sequence their "
        "waves ran under the engine lock: stacked (every run of waves "
        "was probed once, decided once and gathered once: one launch "
        "of each program and one read a run, after the probe found "
        "every lane live) or per_wave (probe, read-through, decide and "
        "row gather wave by wave: a flush of one wave, one that reads "
        "through, one with a RESET_REMAINING lane, a paged table, or a "
        "stacked shape that is not warm).",
        ["sequence"],
    )
    for sequence in STORE_SEQUENCES:
        flushes.labels(sequence).inc(0)
    return {
        "store_gets": gets,
        "store_injected_rows": _BareCounter(
            "gubernator_store_injected_rows",
            "Rows written into the table from a Store.get hit (the "
            "read-through). A row displaced and re-seated inside one "
            "flush comes from that flush's own gathered rows and counts "
            "only as an inject program.",
        ),
        "store_on_change_items": _BareCounter(
            "gubernator_store_on_change_items",
            "Snapshots handed to Store.on_change after a flush: one a "
            "key the flush changed, its last operation winning.",
        ),
        "store_removes": _BareCounter(
            "gubernator_store_removes",
            "Store.remove calls: keys whose last operation in a flush "
            "was a token bucket's RESET_REMAINING.",
        ),
        "store_rows_skipped": _BareCounter(
            "gubernator_store_rows_skipped",
            "Lanes of a flush whose acknowledged change was not handed "
            "to the Store because the row gathered for them was unused "
            "or held another key. 0 wherever the row gather reads the "
            "slots the decide wrote, on one device and on a mesh.",
        ),
        "store_handover_waits": _BareCounter(
            "gubernator_store_handover_waits",
            "Flushes that had to wait for the flush before them to "
            "finish its hand-over to the Store (the reads of its waves' "
            "outputs and rows and its write-behind, after the engine "
            "lock) before they began their own, or before a Store.get "
            "under the engine lock. The wait is under the engine lock: "
            "0 while a hand-over is shorter than the next flush's hold.",
        ),
        "store_flushes": flushes,
        "store_stacked_surprises": _BareCounter(
            "gubernator_engine_store_stacked_surprises",
            "Stacked launches of a Store flush whose output, read after "
            "the engine lock, shows what the stacked sequence rules "
            "out: a lane that missed, a key displaced, a row freed. "
            "Must read 0: a run is stacked only after its one probe "
            "found every lane live and no lane asks for "
            "RESET_REMAINING.",
        ),
    }


def engine_histograms() -> dict:
    us, cnt = 1e-6, 1.0
    return {
        "flush_duration": Log2Histogram(
            "gubernator_engine_flush_duration",
            "Engine flush wall time in seconds (host assembly + device "
            "waves + response demux), by serving path.",
            scale=us, n_buckets=24, labelnames=("path",),
        ),
        "device_sync": Log2Histogram(
            "gubernator_engine_device_sync_duration",
            "Device wave execution + host materialization time per flush "
            "in seconds, by serving path.",
            scale=us, n_buckets=24, labelnames=("path",),
        ),
        "queue_wait": Log2Histogram(
            "gubernator_engine_queue_wait_duration",
            "Time queue entries waited before a pump flush picked them "
            "up, in seconds.",
            scale=us, n_buckets=24,
        ),
        "flush_waves": Log2Histogram(
            "gubernator_engine_flush_waves",
            "Sequential decide() waves per engine flush.",
            scale=cnt, n_buckets=12,
        ),
        "flush_launches": Log2Histogram(
            "gubernator_engine_flush_launches",
            "Decide programs launched per engine flush: a run of "
            "consecutive waves of one width is one operand, one launch "
            "under the engine lock and one read. Over "
            "gubernator_engine_flush_waves it says how far a flush's "
            "waves shared their launches.",
            scale=cnt, n_buckets=12,
        ),
        "flush_calls": Log2Histogram(
            "gubernator_engine_flush_calls",
            "Calls served per engine flush: the members of a columnar "
            "flush (small calls that arrived while another flush was "
            "in its host stage share the next one: one assembly, one "
            "launch, one read), the distinct calls a pump flush "
            "coalesced. _sum / _count is calls a flush; 1 where every "
            "call is its own flush.",
            scale=cnt, n_buckets=12,
        ),
        "batch_width": Log2Histogram(
            "gubernator_engine_batch_width",
            "Requests served per engine flush, by serving path.",
            scale=cnt, n_buckets=16, labelnames=("path",),
        ),
        "pipeline_inflight": Log2Histogram(
            "gubernator_engine_pipeline_inflight",
            "In-flight flush tickets observed at each pump dispatch "
            "(dispatched, not yet completed; bounded by "
            "GUBER_PIPELINE_DEPTH — pinned at 1 in serial mode).",
            scale=cnt, n_buckets=6,
        ),
        "pipeline_overlap": Log2Histogram(
            "gubernator_engine_pipeline_overlap_ratio",
            "Per-flush host/device overlap: host dispatch work done for "
            "OTHER flushes while this one was in flight, as a fraction "
            "of its in-flight window (0 = serial pump, ~1 = host encode "
            "fully hidden behind device execution).",
            scale=1 / 256, n_buckets=10,
        ),
        "collective_tick": Log2Histogram(
            "gubernator_collective_tick_duration",
            "Per-flush collective tick wall time in seconds on "
            "multi-device topologies: device execution + host "
            "materialization of the sharded decide, whose psum merge "
            "rendezvouses every shard — one slow shard stretches every "
            "tick (docs/monitoring.md \"SLOs & burn rates\").",
            scale=us, n_buckets=24,
        ),
        "ici_tick_duration": Log2Histogram(
            "gubernator_ici_tick_duration",
            "ICI GLOBAL sync tick wall time in seconds (collective "
            "dispatch + device sync).",
            scale=us, n_buckets=24,
        ),
        "ici_tick_groups": Log2Histogram(
            "gubernator_ici_tick_groups",
            "Groups merged per ICI GLOBAL sync tick.",
            scale=cnt, n_buckets=26,
        ),
        "ici_tick_width": Log2Histogram(
            "gubernator_ici_tick_width",
            "Width in groups of the block an ICI GLOBAL sync tick "
            "merged at: the least of its ladder that held the groups "
            "it found active (the whole table on a full tick).",
            scale=cnt, n_buckets=26,
        ),
        "ici_tick_stage_duration": Log2Histogram(
            "gubernator_ici_tick_stage_duration",
            "Wall seconds of one ICI GLOBAL sync tick by stage: "
            "lock_wait (asking for the engine lock and the collective "
            "guard until both are held), launch (under them, until the "
            "sync program's launch returns), read (the blocking read "
            "of the tick's diagnostics: the wait for the device). One "
            "observation of each a tick; they add up to "
            "gubernator_ici_tick_duration less its first and last "
            "lines.",
            scale=us, n_buckets=24, labelnames=("stage",),
        ),
        "stage_duration": Log2Histogram(
            "gubernator_engine_stage_duration",
            "Per-stage request-lifecycle latency in seconds, by stage: "
            "intake (submit-side validation until enqueue), assemble "
            "(flush pull to kernel launch), dispatch (async kernel "
            "launch), inflight_wait (dispatched, waiting for the "
            "completion stage), device_sync (host materialization of "
            "device results), resolve (telemetry + write-behind + "
            "future resolution). Before assemble, for a columnar call "
            "that joined the group commit: join (its wait for the flush "
            "that serves it). Inside assemble: hash, waves, keydict. "
            "Inside device_sync on the columnar path (after assemble on "
            "the object path): lock_wait (engine lock + collective "
            "guard), dispatch (the wave launches under the lock), "
            "readback (the blocking read). post is what follows the "
            "read. With a Store attached, a wave inside dispatch: "
            "readthrough (probe, Store.get, inject) and store_rows (the "
            "wave's read and its row gather); a flush inside post: "
            "write_behind (snapshots, Store.remove, Store.on_change).",
            scale=us, n_buckets=24, labelnames=("stage",),
        ),
        "transfer_duration": Log2Histogram(
            "gubernator_transfer_duration",
            "Accounted host<->device transfer wall time in seconds, by "
            "direction (h2d/d2h) and purpose (serve/snapshot/inject/"
            "warmup/census). d2h materializations block, so their time "
            "is the real copy (+ any compute it waits on); h2d puts are "
            "async on accelerators, so their time is dispatch cost "
            "(utils/transfer.py).",
            scale=us, n_buckets=24, labelnames=("direction", "purpose"),
        ),
        "transfer_bytes": Log2Histogram(
            "gubernator_transfer_bytes",
            "Bytes moved per accounted host<->device transfer, by "
            "direction and purpose — with transfer_duration, the "
            "sustainable-bandwidth envelope the paged table's "
            "promote/demote path will ride (ROADMAP item 1).",
            scale=64.0, n_buckets=26, labelnames=("direction", "purpose"),
        ),
        "hotkeys": HotKeySketch(
            "gubernator_hotkey_hits",
            "Estimated hits for the top-K hottest keys (weighted "
            "space-saving sketch, GUBER_HOTKEYS_K entries max; see "
            "/debug/hotkeys for error bounds and over-limit counts).",
        ),
    }


class Metrics:
    def __init__(self, registry: CollectorRegistry | None = None):
        self.registry = registry or CollectorRegistry()
        r = self.registry
        self._bare: list[_BareCounter] = []
        self._renderables: list = []  # Log2Histogram-shaped (render_lines)
        self._claimed: set = set()  # sample names owned outside the registry
        self._sync_fail_counts: dict = {}

        counter = self.bare_counter

        # Core serving metrics (reference gubernator.go:60-111)
        self.getratelimit_counter = counter(
            "gubernator_getratelimit_counter",
            "The count of getLocalRateLimit() calls.",
            ["calltype"],  # local | forward | global
        )
        self.func_duration = Summary(
            "gubernator_func_duration",
            "The timings of key functions in seconds.",
            ["name"],
            registry=r,
        )
        self.over_limit_counter = counter(
            "gubernator_over_limit_counter",
            "The number of rate limit checks that are over the limit. "
            "The bare sample is the engine's total; {path=...} children "
            "split over-limit answers by the serving path that produced "
            "them (decision provenance, docs/monitoring.md "
            '"Admission").',
            ["path"],
        )
        self.concurrent_checks = Gauge(
            "gubernator_concurrent_checks_counter",
            "The number of concurrent GetRateLimits API calls.",
            registry=r,
        )
        self.check_error_counter = counter(
            "gubernator_check_error_counter",
            "The number of errors while checking rate limits.",
            ["error"],
        )

        # Engine (replaces worker-pool metrics, reference gubernator.go:86-93)
        self.worker_queue_length = Gauge(
            "gubernator_worker_queue_length",
            "Requests queued for the device engine.",
            registry=r,
        )
        self.command_counter = counter(
            "gubernator_command_counter",
            "The count of commands processed by the device engine.",
        )

        # Cache (reference lrucache.go:48-59)
        self.cache_access_count = counter(
            "gubernator_cache_access_count",
            "Cache access counts during rate checks.",
            ["type"],  # 'hit' | 'miss'
        )
        self.cache_size = Gauge(
            "gubernator_cache_size",
            "The number of live entries in the counter table.",
            registry=r,
        )
        self.unexpired_evictions = counter(
            "gubernator_unexpired_evictions_count",
            "Count of evictions of unexpired entries (capacity pressure).",
        )

        # Batch behavior (reference gubernator.go:96-110)
        self.batch_send_duration = Summary(
            "gubernator_batch_send_duration",
            "The timings of batch sends to a remote peer in seconds.",
            registry=r,
        )
        self.batch_queue_length = Gauge(
            "gubernator_batch_queue_length",
            "Rate checks queued for batching to remote peers.",
            registry=r,
        )
        self.batch_send_retries = counter(
            "gubernator_batch_send_retries",
            "Retries while forwarding requests to another peer.",
        )

        # Fault domain (docs/robustness.md; no reference analog — the
        # reference burns 5 serial timeouts per request on a dead owner)
        self.circuit_state = Gauge(
            "gubernator_circuit_state",
            "Per-peer circuit breaker state: 0 closed, 1 half-open, "
            "2 open.",
            ["peer"],
            registry=r,
        )
        self.circuit_transitions = counter(
            "gubernator_circuit_transitions",
            "Circuit breaker state transitions, by peer and target state.",
            ["peer", "to"],
        )
        self.degraded_local_answers = counter(
            "gubernator_degraded_local_answers",
            "Forwarded checks answered from local state because the "
            "owner's circuit was open (GUBER_OWNER_UNREACHABLE=local).",
        )
        self.forward_deadline_exceeded = counter(
            "gubernator_forward_deadline_exceeded",
            "Forwarded checks that exhausted their deadline budget "
            "before any peer answered.",
        )
        self.edge_call_timeouts = counter(
            "gubernator_edge_call_timeouts",
            "Edge-tier frame calls that timed out waiting on the device "
            "daemon (edge processes expose this on their own /metrics).",
        )
        self.forward_queue_full = counter(
            "gubernator_forward_queue_full",
            "Forwarded checks shed before leaving this node, by reason: "
            "'queue_full' — the target peer's batch queue was full "
            "(producers never block on a full queue); 'brownout' — the "
            "overload ladder reached degraded-local and answered "
            "locally instead of forwarding.",
            ["reason"],
        )

        # Zero-loss elasticity (docs/robustness.md "Rolling restarts &
        # handover"; no reference analog — the reference accepts counter
        # loss whenever ownership moves)
        self.handover_keys_sent = counter(
            "gubernator_handover_keys_sent",
            "Keys shipped to their new owners during ring-change or "
            "drain handover (TransferSnapshots sender side).",
        )
        self.handover_keys_received = counter(
            "gubernator_handover_keys_received",
            "Handover keys merged into the local table "
            "(TransferSnapshots receiver side, after last-writer-wins).",
        )
        self.handover_keys_dropped = counter(
            "gubernator_handover_keys_dropped",
            "Handover keys NOT transferred, by reason: max_keys (over "
            "GUBER_HANDOVER_MAX_KEYS), circuit_open (target breaker "
            "open), deadline (budget exhausted), send_error (transport "
            "failure), stale (receiver had a newer stamp).",
            ["reason"],
        )
        self.handover_duration = Summary(
            "gubernator_handover_duration",
            "Wall time of one handover pass (snapshot gather + chunked "
            "transfer legs) in seconds.",
            registry=r,
        )

        # Crash-tolerant ownership (docs/robustness.md "Standby
        # replication & crash recovery"; no reference analog — the
        # reference loses every counter an owner holds on hard kill)
        self.standby_loss_bound_hits = Gauge(
            "gubernator_standby_loss_bound_hits",
            "The published hard-kill loss bound: hits dirtied on this "
            "owner since the last ACKED standby delta ship (unacked "
            "pending plus not-yet-drained engine dirt). Killing this "
            "node now loses at most this many hits.",
            registry=r,
        )
        self.standby_keys_shipped = counter(
            "gubernator_standby_keys_shipped",
            "Snapshot rows shipped to ring successors by the standby "
            "replication loop, by mode: delta (dirtied keys), full "
            "(ring-change bootstrap), repair (anti-entropy region "
            "re-ship), legacy (v=1 full-image fallback to a pre-standby "
            "receiver).",
            ["mode"],
        )
        self.standby_ship_errors = counter(
            "gubernator_standby_ship_errors",
            "Standby replication legs that failed, by reason: "
            "circuit_open, deadline, send_error.",
            ["reason"],
        )
        self.standby_shadow_keys = Gauge(
            "gubernator_standby_shadow_keys",
            "Shadow rows this node currently holds for upstream owners "
            "it stands by for (non-serving until promotion).",
            registry=r,
        )
        self.standby_promotions = counter(
            "gubernator_standby_promotions",
            "Standby promotions executed, by reason: breaker_open "
            "(upstream owner's circuit open past "
            "GUBER_STANDBY_PROMOTE_AFTER), ring_removed (owner left the "
            "ring without retiring its shadow).",
            ["reason"],
        )
        self.standby_promoted_keys = counter(
            "gubernator_standby_promoted_keys",
            "Shadow rows replayed at promotion, by destination: local "
            "(merged into this node's table last-writer-wins), "
            "forwarded (shipped to the key's current owner).",
            ["dest"],
        )
        self.standby_anti_entropy_repairs = counter(
            "gubernator_standby_anti_entropy_repairs",
            "Regions re-shipped because the owner/standby digest "
            "exchange found a mismatch (also counted in "
            "gubernator_consistency_divergence kind=standby).",
        )

        # GLOBAL behavior (reference global.go:50-67)
        self.broadcast_duration = Summary(
            "gubernator_broadcast_duration",
            "The timings of GLOBAL broadcasts to peers in seconds.",
            registry=r,
        )
        self.broadcast_counter = counter(
            "gubernator_broadcast_counter",
            "The count of GLOBAL broadcasts.",
        )
        self.global_send_duration = Summary(
            "gubernator_global_send_duration",
            "The timings of GLOBAL hit-update sends to owners in seconds.",
            registry=r,
        )
        self.global_queue_length = Gauge(
            "gubernator_global_queue_length",
            "Requests queued for GLOBAL broadcast.",
            registry=r,
        )
        self.global_send_queue_length = Gauge(
            "gubernator_global_send_queue_length",
            "Requests queued for GLOBAL hit-update send.",
            registry=r,
        )
        # Failure visibility for the async GLOBAL legs: the reference logs
        # every failed send/broadcast leg (global.go:180-186, 278-281);
        # these counters make a persistently failing leg observable at
        # /metrics too.
        self.global_send_errors = counter(
            "gubernator_global_send_errors",
            "Failed GLOBAL hit-update sends to owners.",
        )
        self.global_broadcast_errors = counter(
            "gubernator_global_broadcast_errors",
            "Failed GLOBAL broadcast pushes to peers.",
        )
        self.global_send_dropped = counter(
            "gubernator_global_send_dropped",
            "Aggregated GLOBAL hits dropped from the hit-update queue, "
            "by reason: no_peer (picker raised) or requeue_cap (aged "
            "past the redelivery bound).",
            ["reason"],
        )
        self.global_requeued_hits = counter(
            "gubernator_global_requeued_hits",
            "Aggregated GLOBAL hits merged back into the hit-update "
            "queue after a failed flush leg (redelivered once the "
            "owner recovers).",
        )
        # ICI replica-tier overflow (no reference analog: its owner cache
        # is LRU-unbounded-by-group, lrucache.go; a W-way replica table
        # needs the degraded regime to be observable — see
        # docs/architecture.md "Overflow and drift bounds")
        self.global_overflow_keys = Gauge(
            "gubernator_global_overflow_keys",
            "GLOBAL entries currently degraded to per-replica counting "
            "(owner group full; summed across mesh devices).",
            registry=r,
        )
        self.global_overflow_drops = counter(
            "gubernator_global_overflow_drops_count",
            "Overflow entries dropped at sync under full-group pressure "
            "(local counter and un-synced deltas lost).",
        )
        self.global_sync_backlog = Gauge(
            "gubernator_global_sync_backlog",
            "Active groups beyond the per-tick sync cap "
            "(GUBER_ICI_SYNC_GROUPS) carried to the next tick; sustained "
            "nonzero means GLOBAL convergence is running behind the "
            "sync cadence.",
            registry=r,
        )
        self.global_merged_hits = counter(
            "gubernator_global_merged_hits",
            "Hits that replicas other than the owner took and the sync "
            "tick applied to a bucket its owner held (adoptions of keys "
            "the owner lacked are not counted).",
        )
        self.global_over_admitted_hits = counter(
            "gubernator_global_over_admitted_hits",
            "Of gubernator_global_merged_hits, the hits the owner's "
            "bucket could no longer take when the tick applied them "
            "(max(hits - remaining, 0), whole hits, token and leaky "
            "alike): what the replicas together admitted beyond a limit "
            "between two ticks.",
        )

        # MULTI_REGION behavior (no reference analog — the reference's
        # RegionPicker ships unimplemented, region_picker.go:19-103;
        # these observe the DCN-tier async replication this framework
        # adds on top: parallel/region_sync.py)
        self.region_send_duration = Summary(
            "gubernator_multiregion_send_duration",
            "The timings of MULTI_REGION hit-delta sends to the home "
            "region in seconds.",
            registry=r,
        )
        self.region_broadcast_duration = Summary(
            "gubernator_multiregion_broadcast_duration",
            "The timings of MULTI_REGION authoritative broadcasts to "
            "other regions in seconds.",
            registry=r,
        )
        self.region_broadcast_counter = counter(
            "gubernator_multiregion_broadcast_counter",
            "The count of MULTI_REGION authoritative broadcasts.",
        )
        self.region_send_errors = counter(
            "gubernator_multiregion_send_errors",
            "Failed MULTI_REGION hit-delta sends to the home region.",
        )
        self.region_broadcast_errors = counter(
            "gubernator_multiregion_broadcast_errors",
            "Failed MULTI_REGION broadcast pushes to other regions.",
        )

        # gRPC stats (reference grpc_stats.go:51-62)
        self.grpc_request_counts = counter(
            "gubernator_grpc_request_counts",
            "The count of gRPC requests.",
            ["method", "status"],
        )
        self.grpc_request_duration = Summary(
            "gubernator_grpc_request_duration",
            "The timings of gRPC requests in seconds.",
            ["method"],
            registry=r,
        )
        # The serving event loop and the interpreter lock, probed at
        # 100 Hz while the daemon serves (utils/tracing.py
        # HostProbes), and the CPU one call in sixteen used on its
        # executor thread (service/fastpath.py).
        self.loop_lag = Log2Histogram(
            "gubernator_loop_lag_seconds",
            "How late the serving event loop ran a 10 ms timer: every "
            "hop a call makes through the loop (gRPC's completion "
            "events, run_in_executor's return, the response's send) "
            "waits about this long.",
            scale=1e-6, n_buckets=24,
        )
        self.register_renderable(self.loop_lag)
        self.interpreter_wait = Log2Histogram(
            "gubernator_interpreter_wait_seconds",
            "How far a thread overslept a 10 ms sleep that releases "
            "the interpreter lock: the timer's slack plus the wait to "
            "get the lock back, which every thread pays after each "
            "blocking read, lock acquire and upload.",
            scale=1e-6, n_buckets=24,
        )
        self.register_renderable(self.interpreter_wait)
        self.call_cpu = Log2Histogram(
            "gubernator_call_cpu_seconds",
            "CPU seconds a call's executor thread used inside "
            "try_serve, for one call in sixteen (by its sequence "
            "number), by the path that served it.",
            scale=1e-6, n_buckets=24, labelnames=("path",),
        )
        self.register_renderable(self.call_cpu)
        self.call_cpu_wall = Log2Histogram(
            "gubernator_call_cpu_wall_seconds",
            "Wall seconds of the same interval of the same calls as "
            "gubernator_call_cpu_seconds: wall less CPU less the waits "
            "that have stages of their own is the call's queueing for "
            "the interpreter lock.",
            scale=1e-6, n_buckets=24, labelnames=("path",),
        )
        self.register_renderable(self.call_cpu_wall)
        # One timeline per call (docs/monitoring.md "Tracing the
        # pipeline"): the stages of a GetRateLimits / GetPeerRateLimits
        # handler, observed at its exit under the path that served it.
        self.call_stage_duration = Log2Histogram(
            "gubernator_call_stage_duration",
            "Wall seconds one call spent in each stage of its handler, "
            "by the path that served it; the stages of a call add up "
            "to its gubernator_grpc_request_duration observation.",
            scale=1e-6, n_buckets=24, labelnames=("path", "stage"),
        )
        self.register_renderable(self.call_stage_duration)
        self.edge_calls = counter(
            "gubernator_edge_calls",
            "GetRateLimits / GetPeerRateLimits calls by the path that "
            "served them and, off the columnar path, why; counted "
            "where gubernator_grpc_request_duration is observed.",
            ["path", "reason"],
        )
        # (path, stage) -> histogram child, resolved once; every child
        # is exposed at 0 from start-up.
        self.call_stages = {
            (path, stage): self.call_stage_duration.declare(path, stage)
            for path, stages in CALL_STAGES.items()
            for stage in stages
        }
        for path, reasons in EDGE_REASONS.items():
            for reason in reasons:
                self.edge_calls.labels(path, reason).inc(0)
        # path -> (CPU child, wall child), exposed at 0 like the stages
        self.call_cpu_children = {
            path: (self.call_cpu.declare(path),
                   self.call_cpu_wall.declare(path))
            for path in CALL_STAGES
        }
        self.engine_busy_seconds = counter(
            "gubernator_engine_busy_seconds",
            "Seconds in which at least one flush was between asking "
            "for the engine lock and the end of its readback: host "
            "time in which the engine had work outstanding for the "
            "device, not device time (a blocking read of an idle "
            "device counts in full).",
        )
        self.engine_clock_seconds = Gauge(
            "gubernator_engine_clock_seconds",
            "The clock gubernator_engine_busy_seconds is read on "
            "(perf_counter at scrape); the ratio of their deltas is the "
            "share of the time the engine had work outstanding.",
            registry=r,
        )

        # Device-tier telemetry (docs/monitoring.md; no reference analog:
        # the engine below the Go-shaped service tier is this port's
        # addition, and its invariants need first-class observability).
        self.engine_cold_compiles = counter(
            "gubernator_engine_cold_compile_count",
            "Serving-path kernel dispatches that triggered an XLA "
            "compile. The serving path is warmed at startup and must "
            "never compile; nonzero means the invariant broke.",
        )
        # Device-resource observatory (docs/monitoring.md "Device
        # resources"): HBM accounting gauges fed from the engine's
        # device_memory() snapshot at scrape time — real allocator
        # stats on TPU/GPU, the geometry-estimated fallback on CPU
        # (utils/devicemem.py; the snapshot schema is identical).
        self.device_bytes_in_use = Gauge(
            "gubernator_device_bytes_in_use",
            "Device (HBM) bytes in use: the allocator's number when the "
            "backend reports one, else the sum of the subsystem "
            "estimates.",
            registry=r,
        )
        self.device_bytes_limit = Gauge(
            "gubernator_device_bytes_limit",
            "Device memory capacity in bytes (allocator limit, or the "
            "documented single-chip assumption on stat-less backends).",
            registry=r,
        )
        self.device_headroom_bytes = Gauge(
            "gubernator_device_headroom_bytes",
            "Device memory headroom: bytes_limit - bytes_in_use, "
            "floored at 0 — what the paged table can still grow into.",
            registry=r,
        )
        self.device_subsystem_bytes = Gauge(
            "gubernator_device_subsystem_bytes",
            "Estimated resident device bytes attributed to each named "
            "engine subsystem (slot_table, ici_replicas, census, "
            "pipeline_ring, snapshot_staging).",
            ["subsystem"],
            registry=r,
        )
        self.device_unattributed_bytes = Gauge(
            "gubernator_device_unattributed_bytes",
            "Device bytes in use beyond the subsystem attribution "
            "(allocator overhead, XLA temporaries; 0 on the estimated "
            "fallback by construction).",
            registry=r,
        )
        # Compile telemetry (docs/monitoring.md "Device resources"):
        # process-wide counters bridged from the jax.monitoring
        # listener in runtime/telemetry.py at scrape time.
        self.compile_cache_hits = counter(
            "gubernator_compile_cache_hits",
            "Persistent-compilation-cache hits (a compile satisfied by "
            "deserializing a cached executable; utils/compilecache.py).",
        )
        self.compile_count = counter(
            "gubernator_compile_count",
            "XLA backend compiles observed process-wide — cache misses "
            "plus uncached programs (every one is a retrace; see "
            "/debug/device for per-program attribution).",
        )
        self.compile_duration_seconds = counter(
            "gubernator_compile_duration_seconds",
            "Cumulative wall seconds spent in XLA backend compiles.",
        )
        self.engine_table_occupancy = Gauge(
            "gubernator_engine_table_occupancy",
            "Fraction of device slot-table slots occupied (0-1), "
            "sampled at scrape time.",
            registry=r,
        )
        self.engine_full_group_ratio = Gauge(
            "gubernator_engine_full_group_ratio",
            "Probe pressure: fraction of slot-table groups with every "
            "way occupied (an insert into a full group must evict).",
            registry=r,
        )
        # Table-census families (docs/monitoring.md "Table census"):
        # residency/coldness/churn telemetry for the paged-table roadmap,
        # fed from the engine's TTL-cached table_census() at scrape time.
        self.table_slots = Gauge(
            "gubernator_table_slots",
            "Total device slot-table capacity in slots (all tiers).",
            registry=r,
        )
        self.table_waste_slots = Gauge(
            "gubernator_table_waste_slots",
            "Expired-but-still-resident slots: used slots whose rate "
            "window has fully elapsed (reclaimable without eviction).",
            registry=r,
        )
        self.table_waste_ratio = Gauge(
            "gubernator_table_waste_ratio",
            "gubernator_table_waste_slots as a fraction of capacity.",
            registry=r,
        )
        self.table_cold_slots = Gauge(
            "gubernator_table_cold_slots",
            "Used slots idle for more than `multiplier` x their own "
            "duration — the cold set a paged table would demote.",
            ["multiplier"],
            registry=r,
        )
        self.table_cold_reclaimable_bytes = Gauge(
            "gubernator_table_cold_reclaimable_bytes",
            "HBM a cold tier would reclaim at this idleness multiplier "
            "(cold slots x bytes_per_slot).",
            ["multiplier"],
            registry=r,
        )
        self.table_heatmap_region_min = Gauge(
            "gubernator_table_heatmap_region_min",
            "Used slots in the least-occupied census heatmap region "
            "(the future page axis; full vector at /debug/table).",
            registry=r,
        )
        self.table_heatmap_region_max = Gauge(
            "gubernator_table_heatmap_region_max",
            "Used slots in the most-occupied census heatmap region.",
            registry=r,
        )
        self.table_max_full_run = Gauge(
            "gubernator_table_max_full_run",
            "Longest run of consecutive completely-full groups (probe "
            "pressure hotspot; inserts there must evict).",
            registry=r,
        )
        self.table_churn_inserts_per_s = Gauge(
            "gubernator_table_churn_inserts_per_s",
            "Census churn ledger: slot insertions per second over the "
            "last census interval.",
            registry=r,
        )
        self.table_churn_evictions_per_s = Gauge(
            "gubernator_table_churn_evictions_per_s",
            "Census churn ledger: unexpired evictions per second over "
            "the last census interval.",
            registry=r,
        )
        self.table_churn_recycles_per_s = Gauge(
            "gubernator_table_churn_recycles_per_s",
            "Census churn ledger: overwrite-recycles per second "
            "(inserts that reclaimed an expired/freed resident slot).",
            registry=r,
        )
        # Paged-table residency (docs/architecture.md "Paged table"):
        # fed from the census snapshot's "pages" section, present only
        # when GUBER_TABLE_PAGE_GROUPS enables paging.
        self.table_page_count = Gauge(
            "gubernator_table_page_count",
            "Paged-table pages by state: resident (bound to a physical "
            "HBM frame), demoted (in the host-DRAM cold tier), free "
            "(unbound physical frames).",
            ["state"],
            registry=r,
        )
        self.table_page_moves = Gauge(
            "gubernator_table_page_moves",
            "Cumulative page residency transitions: demote (d2h "
            "evacuation to the host tier), promote (h2d refill from the "
            "host tier), bind (fresh zeroed frame for a never-resident "
            "page).",
            ["kind"],
            registry=r,
        )
        self.table_page_host_bytes = Gauge(
            "gubernator_table_page_host_bytes",
            "Host-DRAM bytes held by demoted pages (wide slot rows).",
            registry=r,
        )
        self.table_slot_age_seconds = CensusSnapshotHistogram(
            "gubernator_table_slot_age_seconds",
            "Census snapshot: resident slots by age (now - stamp; time "
            "since the counter window was created/updated).",
        )
        self.register_renderable(self.table_slot_age_seconds)
        self.table_slot_idle_seconds = CensusSnapshotHistogram(
            "gubernator_table_slot_idle_seconds",
            "Census snapshot: resident slots by idle time (now - lru; "
            "time since the slot last served a request).",
        )
        self.register_renderable(self.table_slot_idle_seconds)
        self.global_broadcast_keys = Log2Histogram(
            "gubernator_global_broadcast_keys",
            "Keys per GLOBAL authoritative broadcast flush.",
            scale=1.0, n_buckets=16,
        )
        self.register_renderable(self.global_broadcast_keys)
        self.global_send_keys = Log2Histogram(
            "gubernator_global_send_keys",
            "Keys per GLOBAL hit-update flush to owners.",
            scale=1.0, n_buckets=16,
        )
        self.register_renderable(self.global_send_keys)

        # Consistency observatory (docs/monitoring.md "Consistency"; no
        # reference analog — the reference takes GLOBAL reconvergence on
        # faith, global.go has no propagation telemetry at all).
        self.global_propagation_lag = Log2Histogram(
            "gubernator_global_propagation_lag",
            "End-to-end GLOBAL propagation lag in seconds: origin stamp "
            "at the hit's enqueue (one sampled probe per flush) to the "
            "replica applying the owner's broadcast. Cross-node wall "
            "clocks; read alongside gubernator_peer_clock_skew_ms.",
            scale=1e-3, n_buckets=24,
        )
        self.register_renderable(self.global_propagation_lag)
        self.global_sync_leg_duration = Log2Histogram(
            "gubernator_global_sync_leg_duration",
            "Per-leg GLOBAL sync timings in seconds: hit_queue_wait "
            "(enqueue to hit-update flush), owner_apply (owner engine "
            "apply of a relayed batch), broadcast_fanout (owner enqueue "
            "to broadcast push done), replica_inject (replica applying "
            "an UpdatePeerGlobals push).",
            scale=1e-6, n_buckets=24, labelnames=("leg",),
        )
        self.register_renderable(self.global_sync_leg_duration)
        self.global_requeue_age = Log2Histogram(
            "gubernator_global_requeue_age",
            "Redelivery attempts at each GLOBAL hit-update requeue — "
            "pressure before GUBER_GLOBAL_REQUEUE_LIMIT drops begin.",
            scale=1.0, n_buckets=8,
        )
        self.register_renderable(self.global_requeue_age)
        self.consistency_divergence = counter(
            "gubernator_consistency_divergence",
            "Owner-vs-replica divergences found by the background "
            "auditor, by kind: lag (replica missed the owner's last "
            "broadcast past the grace window), "
            "lost (owner key absent at the replica past the grace "
            "window), conflict (transport current and stamps match but "
            "remaining differs).",
            ["kind"],
        )
        self.consistency_max_staleness = Gauge(
            "gubernator_consistency_max_staleness_ms",
            "Max owner-vs-replica staleness (ms) observed in the last "
            "audit pass; falls back toward 0 after reconvergence.",
            registry=r,
        )
        self.peer_clock_skew = Gauge(
            "gubernator_peer_clock_skew_ms",
            "Estimated wall-clock skew to each peer (remote now minus "
            "local RPC midpoint, ms) — the honesty bound for the "
            "stamp-based propagation-lag histogram.",
            ["peer"],
            registry=r,
        )
        self.ici_full_ticks = counter(
            "gubernator_ici_full_ticks",
            "Forced full-table ICI sync ticks (the fingerprint-collision "
            "backstop, every GUBER_ICI_FULL_TICK_EVERY capped ticks).",
        )

        # Cooperative token leases (docs/monitoring.md "Leases";
        # GUBER_LEASES — all zero when leases are off).
        self.lease_grants = counter(
            "gubernator_lease_grants",
            "Lease grant decisions by result: granted, rejected "
            "(ineligible / over limit / table full), revoked (key is "
            "under an active revocation window).",
            ["result"],
        )
        self.lease_hits = counter(
            "gubernator_lease_hits",
            "Lease ledger flows in hit units: granted (carved from the "
            "slot), returned (slice came back — renew or final), "
            "credited (unused tokens restored to the slot), expired "
            "(reclaimed by the sweep or a revocation; unused tokens are "
            "forfeit). Conservation: granted - returned - expired == "
            "outstanding.",
            ["kind"],
        )
        self.lease_outstanding_hits = Gauge(
            "gubernator_lease_outstanding_hits",
            "Hits currently out on lease (granted - returned - expired) "
            "— the fleet-wide over-admission bound during a partition; "
            "its return to 0 after heal is the lease reconvergence "
            "signal (auditor lease pass).",
            registry=r,
        )
        self.lease_revocations = counter(
            "gubernator_lease_revocations",
            "Lease revocations broadcast by this owner (an over-limit "
            "re-read found outstanding slices on the key).",
        )
        self.lease_local_answers = counter(
            "gubernator_lease_local_answers",
            "Checks answered entirely from a local lease slice (zero "
            "RPCs) by a holder-side cache colocated with this registry "
            "(edge tier).",
        )

        # Admission observatory (docs/monitoring.md "Admission"):
        # decision provenance + ground-truth enforcement-error SLIs.
        self.admission_decisions = counter(
            "gubernator_admission_decisions",
            "Rate-limit answers by the serving path that produced them "
            "(owner | replica | degraded_local | lease | fastpath | "
            "forwarded) and resulting status (under_limit | over_limit "
            "| error).",
            ["path", "status"],
        )
        self.admission_excess_ratio = Gauge(
            "gubernator_admission_excess_ratio",
            "Over-admission SLI for this node: hits admitted beyond "
            "configured limits per configured limit hit, from the "
            "TTL-cached admission scan reconciled with the lease "
            "ledger's outstanding slices and this node's un-relayed "
            "GLOBAL hits; falls back to 0 after heal.",
            registry=r,
        )
        self.admission_audit_max_excess_ratio = Gauge(
            "gubernator_admission_audit_max_excess_ratio",
            "Max over-admission ratio seen in the last audit pass "
            "across this owner and the sampled replica (auditor "
            "admission pass); re-set every cycle, so its return to 0 "
            "after heal is the enforcement reconvergence signal.",
            registry=r,
        )
        self.admission_false_over_limit = Gauge(
            "gubernator_admission_false_over_limit_keys",
            "Under-admission SLI: sampled keys the last audit pass saw "
            "refused (OVER_LIMIT) at a transport-current replica while "
            "the owner still had remaining budget; re-set every pass, "
            "falls back to 0 after reconvergence.",
            registry=r,
        )
        self.admission_excess_hits = AdmissionExcessHistogram(
            "gubernator_admission_excess_hits",
            "Per-window excess snapshot: resident keys by hits "
            "admitted beyond their configured limit (log2 hit buckets; "
            "re-published per admission scan — the CURRENT population, "
            "not a cumulative event stream).",
        )
        self.register_renderable(self.admission_excess_hits)

        # SLO observatory (docs/monitoring.md "SLOs & burn rates",
        # service/slo.py): multi-window burn rates per SLO spec, error
        # budget remaining over each spec's budget window, and the
        # alert state machine (0 ok | 1 slow_burn | 2 fast_burn |
        # 3 exhausted). All set by the _slo_sync scrape bridge from the
        # observatory's host-side rings — zero device work.
        self.slo_burn_rate = Gauge(
            "gubernator_slo_burn_rate",
            "Error-budget burn rate per SLO and evaluation window: "
            "bad-event fraction over the window divided by the SLO's "
            "error budget (1 - objective). 1.0 = burning exactly at "
            "budget; the fast-burn alert fires around 14.4x.",
            ["slo", "window"],
            registry=r,
        )
        self.slo_error_budget_remaining = Gauge(
            "gubernator_slo_error_budget_remaining",
            "Fraction of the SLO's error budget left over its budget "
            "window (1.0 = untouched, 0 = exhausted, clamped at 0).",
            ["slo"],
            registry=r,
        )
        self.slo_alert_state = Gauge(
            "gubernator_slo_alert_state",
            "SLO alert state machine: 0 ok, 1 slow_burn (both "
            "slow-burn windows over threshold), 2 fast_burn (both "
            "fast-burn windows over), 3 exhausted (budget fully "
            "burned).",
            ["slo"],
            registry=r,
        )
        # Self-watchdog (runtime/watchdog.py): per-loop stall flags,
        # set by the _slo_sync bridge from the watchdog's heartbeat
        # table. A serving loop's stall also burns the availability
        # SLO — this gauge is the per-loop attribution.
        self.thread_stalled = Gauge(
            "gubernator_thread_stalled",
            "1 when the named long-lived loop's heartbeat is older "
            "than its stall deadline (GUBER_WATCHDOG_STALL_MS + the "
            "loop's declared period), else 0.",
            ["loop"],
            registry=r,
        )
        # Shard-skew attribution (mesh topologies): max/mean imbalance
        # across per-shard decisions / occupancy / resident frames —
        # 1.0 is perfectly balanced; feeds the shard-balance SLO and
        # the future PodSliceTopology placement work (ROADMAP item 1).
        self.shard_imbalance_ratio = Gauge(
            "gubernator_shard_imbalance_ratio",
            "Worst max/mean imbalance across shards of the mesh "
            "(decisions served, census occupancy, resident page "
            "frames); 1.0 = balanced, absent on single-device "
            "topologies.",
            registry=r,
        )
        self.replica_decisions = counter(
            "gubernator_replica_decisions",
            "GLOBAL lanes the replica tier answered, by the home device "
            "the host assigned (round-robin), columnar and object path "
            "alike; the series sum to the replica lanes answered. "
            "Absent on engines without a replica tier.",
            ["device"],
        )
        self.shard_decisions = counter(
            "gubernator_shard_decisions",
            "Lanes the owner-sharded decide answered on each shard of "
            "the mesh (shard = group // groups per shard), columnar and "
            "object path alike; the series sum to the sharded lanes "
            "answered. Absent on single-device topologies.",
            ["shard"],
        )

        # Overload control plane (service/overload.py; GUBER_OVERLOAD —
        # docs/robustness.md "Overload control & brownout").
        self.overload_level = Gauge(
            "gubernator_overload_level",
            "Brownout ladder level: 0 normal, 1 shed observability "
            "extras, 2 answer would-be peer forwards locally "
            "(degraded-local), 3 shed heavy-hitter tenants outright.",
            registry=r,
        )
        self.overload_transitions = counter(
            "gubernator_overload_transitions",
            "Brownout ladder transitions, labeled with the level "
            "ENTERED (escalations and recoveries both count).",
            ["level"],
        )
        self.intake_shed_counter = counter(
            "gubernator_intake_shed_counter",
            "Requests refused by the intake governor before any device "
            "work, by reason: queue_full (depth >= GUBER_INTAKE_LIMIT), "
            "deadline_expired (caller deadline passed at admit or "
            "pickup), codel (standing queue above GUBER_INTAKE_TARGET_MS), "
            "tenant (same controller, dominant-tenant multiplier), "
            "brownout (ladder level 3 heavy-tenant shed).",
            ["reason"],
        )

        self._syncs = []

    # -- registration --------------------------------------------------------

    def _claim_names(self, names) -> None:
        """Reject sample names that collide with the registry or with
        already-registered bare counters / renderables: duplicate sample
        names corrupt the scrape (two families with the same name parse
        as one), so collision is a registration-time error, never a
        runtime surprise."""
        existing = set(self._claimed)
        try:
            existing |= set(self.registry._names_to_collectors)
        except Exception:  # pragma: no cover - private API drift
            pass
        for n in names:
            if n in existing:
                raise ValueError(
                    f"duplicate metric sample name {n!r}: already "
                    "registered with this Metrics registry"
                )
        self._claimed.update(names)

    def bare_counter(self, name, doc, labels=()) -> _BareCounter:
        """A counter exposed under its bare Go name (see _BareCounter);
        name-guarded against the whole registry."""
        self._claim_names([name])
        c = _BareCounter(name, doc, labels)
        self._bare.append(c)
        return c

    def register_renderable(self, h) -> None:
        """Register an externally-owned series (engine Log2Histograms)
        for exposition through render(); name-guarded like bare
        counters."""
        self._claim_names(h.sample_names())
        self._renderables.append(h)

    def sample_family_names(self) -> set:
        """Every sample FAMILY this Metrics instance exposes — the audit
        surface for tools/check_metrics_names.py."""
        names = {c.name for c in self._bare}
        names |= {h.name for h in self._renderables}
        for fam in self.registry.collect():
            names.add(fam.name)
        return names

    def add_sync(self, fn) -> None:
        """Register a callback run before each exposition (bridges engine
        counters into the registry at scrape time)."""
        self._syncs.append(fn)

    def sync(self) -> None:
        for i, fn in enumerate(self._syncs):
            try:
                fn(self)
            except Exception:
                # A broken bridge must be diagnosable, not a silent
                # flatline — log the first failure per callback (and
                # every 1000th, in case the cause changes later).
                n = self._sync_fail_counts.get(i, 0) + 1
                self._sync_fail_counts[i] = n
                if n == 1 or n % 1000 == 0:
                    log.exception(
                        "metrics sync callback %r failed (failure %d; "
                        "its series are stale until it recovers)", fn, n,
                    )

    def render(self, openmetrics: bool = False) -> bytes:
        self.sync()
        lines = []
        for c in self._bare:
            lines.extend(c.render_lines())
        for h in self._renderables:
            try:
                lines.extend(h.render_lines(openmetrics=openmetrics))
            except TypeError:  # externally-owned renderable, old shape
                lines.extend(h.render_lines())
        text = ("\n".join(lines) + "\n").encode() if lines else b""
        body = text + generate_latest(self.registry)
        if openmetrics:
            body += b"# EOF\n"
        return body

    def render_negotiated(self, accept: str = "") -> tuple:
        """(body, content_type) for one scrape, honoring OpenMetrics
        content negotiation: exemplars are an OpenMetrics construct, so
        they render ONLY when the scraper asks for
        application/openmetrics-text (Prometheus does once exemplar
        storage is enabled). Plain scrapes stay byte-stable."""
        if OPENMETRICS_CONTENT_TYPE.split(";")[0] in (accept or ""):
            return self.render(openmetrics=True), OPENMETRICS_CONTENT_TYPE
        return self.render(), CONTENT_TYPE_LATEST

    content_type = CONTENT_TYPE_LATEST


OPENMETRICS_CONTENT_TYPE = (
    "application/openmetrics-text; version=1.0.0; charset=utf-8"
)


def engine_sync(engine):
    """Sync callback exporting DeviceEngine counters under the reference's
    cache/worker metric names (reference lrucache.go:48-59,
    gubernator.go:86-93), plus the device-tier gauges this port adds
    (occupancy / probe pressure / cold compiles / the table-census
    families). Table residency reads the engine's TTL-cached
    table_census() — a scrape never triggers device work itself
    (guberlint GL009; docs/monitoring.md "Table census")."""

    def _sync(m: "Metrics") -> None:
        em = engine.metrics
        m.cache_access_count.labels("hit").set(em.cache_hits)
        m.cache_access_count.labels("miss").set(em.cache_misses)
        m.unexpired_evictions.set(em.unexpired_evictions)
        m.over_limit_counter.set(em.over_limit)
        m.command_counter.set(em.requests)
        m.worker_queue_length.set(engine.queue_depth())
        m.engine_cold_compiles.set(getattr(em, "cold_compiles", 0))
        if hasattr(em, "busy_clock"):
            busy, clock = em.busy_clock()
            m.engine_busy_seconds.set(busy)
            m.engine_clock_seconds.set(clock)
        if hasattr(engine, "table_census"):
            c = engine.table_census()
            m.cache_size.set(c["live"])
            m.engine_table_occupancy.set(c["occupancy"])
            m.engine_full_group_ratio.set(c["full_group_ratio"])
            m.table_slots.set(c["slots"])
            m.table_waste_slots.set(c["waste"])
            m.table_waste_ratio.set(c["waste_frac"])
            for entry in c["cold"]:
                mult = str(entry["multiplier"])
                m.table_cold_slots.labels(mult).set(entry["slots"])
                m.table_cold_reclaimable_bytes.labels(mult).set(
                    entry["reclaimable_bytes"]
                )
            heat = c["heatmap"]
            if heat:
                m.table_heatmap_region_min.set(min(heat))
                m.table_heatmap_region_max.set(max(heat))
            m.table_max_full_run.set(c["max_full_run"])
            churn = c.get("churn") or {}
            m.table_churn_inserts_per_s.set(churn.get("insert_per_s", 0.0))
            m.table_churn_evictions_per_s.set(churn.get("evict_per_s", 0.0))
            m.table_churn_recycles_per_s.set(churn.get("recycle_per_s", 0.0))
            m.table_slot_age_seconds.update(c["age_ms_hist"], c["age_ms_sum"])
            m.table_slot_idle_seconds.update(
                c["idle_ms_hist"], c["idle_ms_sum"]
            )
            # Admission accounting rides the same scrape bridge: the
            # TTL-cached snapshot feeds the excess histogram (the
            # reconciled SLI gauges are set by the service-level sync /
            # auditor, which also see the lease + GLOBAL ledgers).
            if hasattr(engine, "admission_snapshot"):
                a = engine.admission_snapshot()
                m.admission_excess_hits.update(
                    a["excess_hist"], a["excess_hits"]
                )
            pages = c.get("pages")
            if pages:
                m.table_page_count.labels("resident").set(pages["resident"])
                m.table_page_count.labels("demoted").set(pages["host"])
                m.table_page_count.labels("free").set(pages["free"])
                m.table_page_moves.labels("demote").set(pages["demotes"])
                m.table_page_moves.labels("promote").set(pages["promotes"])
                m.table_page_moves.labels("bind").set(pages["binds"])
                m.table_page_host_bytes.set(pages["host_bytes"])
        elif hasattr(engine, "occupancy_stats"):
            stats = engine.occupancy_stats()
            m.cache_size.set(stats["live"])
            m.engine_table_occupancy.set(stats["occupancy"])
            m.engine_full_group_ratio.set(stats["full_group_ratio"])
        else:
            m.cache_size.set(engine.live_count())
        if hasattr(engine, "shard_stats"):
            # Shard-skew attribution (mesh topologies only): host
            # counters + the ALREADY-CACHED census — shard_stats never
            # scans, so this stays zero-device-work even when the
            # census cache is cold (it just omits occupancy then).
            ss = engine.shard_stats()
            if ss is not None:
                for shard, lanes in enumerate(ss["decisions"]):
                    m.shard_decisions.labels(shard).set(lanes)
                if ss.get("imbalance_ratio") is not None:
                    m.shard_imbalance_ratio.set(ss["imbalance_ratio"])
                for dev, lanes in enumerate(ss["replica_decisions"]):
                    m.replica_decisions.labels(dev).set(lanes)
        if hasattr(engine, "overflow_keys"):  # ici-mode engines only
            m.global_overflow_keys.set(engine.overflow_keys)
            m.global_overflow_drops.set(engine.overflow_drops)
            m.global_sync_backlog.set(getattr(engine, "sync_backlog", 0))
            m.global_merged_hits.set(getattr(engine, "merged_hits", 0))
            m.global_over_admitted_hits.set(
                getattr(engine, "over_admitted_hits", 0)
            )
            m.ici_full_ticks.set(getattr(engine, "full_ticks", 0))
        if hasattr(engine, "device_memory"):
            # Host-side arithmetic over static geometry + one allocator
            # stats query — no device program runs (GL009 stays clean).
            d = engine.device_memory()
            m.device_bytes_in_use.set(d["bytes_in_use"])
            m.device_bytes_limit.set(d["bytes_limit"])
            m.device_headroom_bytes.set(d["headroom_bytes"])
            m.device_unattributed_bytes.set(d["unattributed_bytes"])
            for name, b in d["subsystems"].items():
                m.device_subsystem_bytes.labels(name).set(b)
        # Compile telemetry is process-global (the jax.monitoring
        # listener); bridging it from every engine's sync is an
        # idempotent monotonic set. Lazy import: the runtime package
        # pulls jax, and catalog_names() must import without it.
        from gubernator_tpu.runtime import telemetry as _rt

        cc = _rt.compile_counters()
        m.compile_cache_hits.set(cc["cache_hits"])
        m.compile_count.set(cc["compiles"])
        m.compile_duration_seconds.set(cc["compile_seconds"])

    return _sync


def wire_engine_telemetry(metrics: "Metrics", engine) -> None:
    """Attach an engine to a Metrics instance: register its device-tier
    histogram series for exposition and add the scalar sync bridge.
    The daemon's composition root calls this once per engine."""
    em = engine.metrics
    for h in getattr(em, "histograms", lambda: ())():
        metrics.register_renderable(h)
        if h is getattr(em, "flush_waves", None):
            metrics.register_renderable(em.wave_transfers)
        if h is getattr(em, "flush_launches", None):
            metrics.register_renderable(em.wave_programs)
            metrics.register_renderable(em.store_wave_crossings)
            metrics.register_renderable(em.flushes_over_max_waves)
    for c in getattr(em, "store_counters", ()):
        metrics.register_renderable(c)
    metrics.add_sync(engine_sync(engine))


def catalog_names() -> set:
    """Every sample family a default-configured daemon can expose at
    /metrics (optional GUBER_METRIC_FLAGS process/runtime collectors
    excluded). tools/check_metrics_names.py pins docs/monitoring.md to
    this set. Deliberately jax-free: only prometheus_client is
    imported."""
    names = Metrics().sample_family_names()
    names |= {h.name for h in engine_histograms().values()}
    names.add(engine_wave_transfers().name)
    names.add(engine_wave_programs().name)
    names.add(engine_store_wave_crossings().name)
    names.add(engine_flushes_over_max_waves().name)
    names |= {c.name for c in engine_store_counters().values()}
    return names
