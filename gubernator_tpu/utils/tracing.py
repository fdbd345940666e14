"""Tracing: OpenTelemetry spans + cross-peer context propagation.

The reference instruments every significant function with OTel spans and
rides trace context across peers inside each rate limit's metadata map
via a TextMapCarrier (reference metadata_carrier.go:19-40,
peer_client.go:358-360 inject, gubernator.go:503-504 extract). Same
model here:

- The OTel *API* is used for spans; without an SDK configured they are
  no-ops (the reference similarly only exports when OTEL_* env vars
  configure an exporter, docs/tracing.md:10-41).
- propagate_inject/extract move W3C traceparent through the request's
  metadata dict, so spans stitch across the peer-forwarding hop.
- stage() times one layer boundary of a call for three readers at once:
  a /metrics histogram (always), the profiler's own timeline while a
  capture runs (set_capturing), and an OTel child span at DEBUG
  (docs/monitoring.md "Tracing the pipeline").
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Dict, Optional

try:
    from opentelemetry import context as _otel_context
    from opentelemetry import trace as _otel_trace
    from opentelemetry.propagate import extract as _extract
    from opentelemetry.propagate import inject as _inject

    _TRACER = _otel_trace.get_tracer("gubernator_tpu")
    _OTEL = True
except Exception:  # pragma: no cover - otel not installed
    _OTEL = False
    _TRACER = None


# Span verbosity (reference GUBER_TRACING_LEVEL, config.go:717-752): at
# INFO (default) the reference filters out noisy per-peer/healthcheck
# spans; DEBUG keeps everything; ERROR keeps only spans created with
# level="ERROR" — the failure-path spans (the reference's holster
# tracing levels spans at creation the same way).
_LEVELS = {"ERROR": 0, "INFO": 1, "DEBUG": 2}
_LEVEL = 1


def set_trace_level(level: str) -> None:
    global _LEVEL
    _LEVEL = _LEVELS.get(str(level).upper(), 1)


def get_trace_level() -> str:
    return {v: k for k, v in _LEVELS.items()}[_LEVEL]


@contextlib.contextmanager
def span(name: str, level: str = "INFO", **attributes):
    """Named scope (the reference's tracing.StartNamedScope analog).

    `level` tags the span's verbosity at creation: spans above the
    configured GUBER_TRACING_LEVEL are skipped entirely (the reference
    drops per-peer/healthcheck spans below DEBUG, config.go:736-752).
    Failure paths create level="ERROR" spans, which survive every
    configured level."""
    if not _OTEL or _LEVELS.get(str(level).upper(), 1) > _LEVEL:
        yield None
        return
    with _TRACER.start_as_current_span(name) as s:
        for k, v in attributes.items():
            try:
                s.set_attribute(k, v)
            except Exception:
                pass
        yield s


# ---------------------------------------------------------------------------
# Batch-aware span lifecycle (docs/monitoring.md "Tracing the pipeline").
#
# The two-stage engine pipeline dispatches a flush on the pump thread and
# completes it on the completion thread, possibly tickets later — a plain
# `with span(...)` cannot cover that. These helpers split the span
# lifecycle: start_span() creates a non-current span at dispatch,
# context_of() captures an attachable context the _FlushTicket carries
# across the thread boundary, and end_span() closes it at completion.
# Every helper is a cheap no-op (None in, None out) when the OTel API is
# absent, no SDK is configured, or the span's level is filtered — the
# knob-off serving path allocates nothing.


def current_span():
    """The active *recording* span, or None. One call per intake (per
    check_bulk / check_async, never per item): the engine captures the
    request span here so the flush that eventually serves the batch can
    link back to it across the batch boundary."""
    if not _OTEL:
        return None
    try:
        s = _otel_trace.get_current_span()
        if s.is_recording():
            return s
    except Exception:
        pass
    return None


def start_span(name: str, level: str = "INFO", **attributes):
    """Start (but do not make current) a span, or None when tracing is
    off / the level is filtered / no SDK records spans. The caller owns
    the lifecycle: make it current with use_span_ctx(), carry
    context_of() across threads, finish with end_span()."""
    if not _OTEL or _LEVELS.get(str(level).upper(), 1) > _LEVEL:
        return None
    try:
        s = _TRACER.start_span(name)
        if not s.is_recording():
            return None  # no SDK: INVALID_SPAN — skip the bookkeeping
        for k, v in attributes.items():
            try:
                s.set_attribute(k, v)
            except Exception:
                pass
        return s
    except Exception:
        return None


@contextlib.contextmanager
def use_span_ctx(s):
    """Make an explicitly-started span current for a scope WITHOUT
    ending it on exit (the flush span outlives its dispatch scope)."""
    if not _OTEL or s is None:
        yield s
        return
    with _otel_trace.use_span(
        s, end_on_exit=False, record_exception=False,
        set_status_on_exception=False,
    ):
        yield s


def context_of(s):
    """An attachable Context with `s` current — what a _FlushTicket
    carries so the completion thread can re-attach the dispatch-time
    trace context (tracing.attached)."""
    if not _OTEL or s is None:
        return None
    try:
        return _otel_trace.set_span_in_context(s)
    except Exception:
        return None


def end_span(s, error=None) -> None:
    """Finish an explicitly-started span, recording `error` (an
    exception) as span status when given. Safe on None and safe to call
    at most once per span from exactly one thread (the completion
    stage)."""
    if not _OTEL or s is None:
        return
    try:
        if error is not None:
            try:
                s.record_exception(error)
                if hasattr(_otel_trace, "StatusCode"):
                    s.set_status(_otel_trace.StatusCode.ERROR)
            except Exception:
                pass
        s.end()
    except Exception:
        pass


def link(src, dst) -> None:
    """Add a span link src -> dst across the batch boundary (request
    span -> flush span and back). Both may be None; add_link needs
    OTel API >= 1.23 and degrades to a no-op below that."""
    if not _OTEL or src is None or dst is None:
        return
    try:
        add = getattr(src, "add_link", None)
        if add is not None:
            add(dst.get_span_context())
    except Exception:
        pass


def trace_id_of(s) -> str:
    """32-hex trace id of a recording+sampled span (the flight-recorder
    join key and the OpenMetrics exemplar payload), or ''. Only sampled
    traces qualify — an exemplar pointing at a never-exported trace is
    a dead link in Grafana."""
    if not _OTEL or s is None:
        return ""
    try:
        sc = s.get_span_context()
        if sc.is_valid and sc.trace_flags.sampled:
            return format(sc.trace_id, "032x")
    except Exception:
        pass
    return ""


def propagate_inject(metadata: Dict[str, str]) -> Dict[str, str]:
    """Inject current trace context into a rate limit's metadata map
    (reference MetadataCarrier inject side). Fast-path: skip the
    propagator machinery entirely when no span context is active
    (~6µs/item otherwise, pure overhead without an SDK). NOTE: this
    also skips non-trace propagators (e.g. baggage) in the no-span
    case; configure tracing if baggage-only propagation matters."""
    if _OTEL:
        try:
            if not _otel_trace.get_current_span().get_span_context().is_valid:
                return metadata
            _inject(metadata)
        except Exception:
            pass
    return metadata


def propagate_extract(metadata: Dict[str, str]):
    """Extract trace context from a forwarded rate limit's metadata
    (reference MetadataCarrier extract side). Returns an attachable
    context or None."""
    if not _OTEL or not metadata:
        return None
    try:
        return _extract(metadata)
    except Exception:
        return None


@contextlib.contextmanager
def attached(ctx):
    if not _OTEL or ctx is None:
        yield
        return
    token = _otel_context.attach(ctx)
    try:
        yield
    finally:
        _otel_context.detach(token)


# ---------------------------------------------------------------------------
# Stage timing: one site, three readers (docs/monitoring.md "Tracing the
# pipeline"). A stage's sink decides which /metrics series the interval
# lands in: EngineMetrics' FlushStages observes per flush as it goes, a
# CallRecord holds a call's stages until its handler knows which path
# served it.

_CALL_SEQ = itertools.count(1)

# True between the profiler's start_trace and stop_trace
# (service/profiler.capture sets it; one capture runs at a time).
_capturing = False


def set_capturing(on: bool) -> None:
    global _capturing
    _capturing = on


def capturing() -> bool:
    return _capturing


def _annotation(name: str, attrs: dict):
    """A jax.profiler.TraceAnnotation: a span in plane /host:CPU of the
    running capture, on the device planes' clock and on the line of the
    thread that opens it. Built only while a capture runs."""
    import jax

    return jax.profiler.TraceAnnotation(name, **attrs)


def _debug_spans() -> bool:
    return _OTEL and _LEVEL >= 2


def open_live(name: str, attrs: dict, otel: bool = True):
    """Open the two readers of a stage that are there only sometimes:
    the capture's span and the SDK's DEBUG span. None, the usual case,
    when neither is on; else what close_live() takes. `otel` False
    leaves the SDK out: a wait that no call owns (the sync tick's, the
    completion thread's) would be a root of its own there, and a
    CallRecord mark makes its stage's SDK span itself."""
    debug = otel and _debug_spans()
    if not _capturing and not debug:
        return None
    live = []
    if _capturing:
        live.append(_annotation(name, attrs))
    if debug:
        live.append(span(name, level="DEBUG", **attrs))
    for cm in live:
        cm.__enter__()
    return live


def close_live(live, *exc) -> None:
    for cm in reversed(live):
        cm.__exit__(*(exc or (None, None, None)))


def next_live(live, name: str = "", otel: bool = True):
    """For a site that takes its own clock marks between spans that
    follow each other on its thread: close `live` (None: nothing is
    open) and open `name`, or nothing."""
    if live is not None:
        close_live(live)
    return open_live(name, {}, otel) if name else None


class stage:
    """`with stage("flush.hash", sink, ids):` times the body on the wall
    clock and hands the interval to `sink.add` under the name's last
    part. The body must stay on one thread and hold no `await`: the
    profiler nests spans per thread. A site that cannot afford the
    object (under the engine lock) takes the clock marks itself and
    uses open_live/close_live."""

    __slots__ = ("name", "sink", "attrs", "_t0", "_live")

    def __init__(self, name: str, sink, attrs: Optional[dict] = None):
        self.name = name
        self.sink = sink
        self.attrs = attrs or {}

    def __enter__(self):
        self._live = open_live(self.name, self.attrs)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._live is not None:
            close_live(self._live, *exc)
        self.sink.add(self.name.rpartition(".")[2], self._t0, t1)
        return False


def _interval_span(name: str, t0_ns: int, t1_ns: int, ctx, attrs: dict):
    """An OTel span for an interval that is already over (a wait between
    threads has no body to wrap), placed by the exporter's clock."""
    off = time.time_ns() - time.perf_counter_ns()
    try:
        s = _TRACER.start_span(name, context=ctx, start_time=t0_ns + off)
        if s.is_recording():
            for k, v in attrs.items():
                s.set_attribute(k, v)
        s.end(end_time=t1_ns + off)
    except Exception:
        pass


class CallRecord:
    """One RPC's timeline: made at handler entry, handed to the fast
    edge, the service and the engine, observed at handler exit when the
    path that served the call is known. Its stages partition the
    handler's time: each ends where the next begins (`add` and `mark`
    both close the interval since the previous boundary), and the
    return to the handler's exit belongs to the last one.

    `sink` maps (path, stage) to a histogram child; `kind` prefixes the
    path label (PeersV1 calls)."""

    __slots__ = (
        "seq", "ids", "kind", "path", "reason", "otel_ctx", "t0", "cursor",
        "_sink", "_stages", "_last", "_live",
    )

    def __init__(self, sink, kind: str = ""):
        self.seq = next(_CALL_SEQ)
        self.ids = {"call": self.seq}
        self.kind = kind
        # Until try_serve says otherwise: the fast edge is off
        # (fastpath.enabled), and the object path serves the call.
        self.path = "object"
        self.reason = "disabled"
        self.otel_ctx = None
        self.t0 = self.cursor = 0
        self._sink = sink
        self._stages: Dict[str, int] = {}
        self._last = ""
        self._live = None

    def begin(self, t_ns: int) -> None:
        self.t0 = self.cursor = t_ns

    def attempt_refused(self) -> None:
        """The columnar attempt was refused (`served` says why): to the
        object path that now serves the call the attempt is one stage,
        from the handler's entry, so the stages so far are forgotten."""
        self._stages.clear()
        self.cursor = self.t0
        self.mark("columnar_attempt")

    def add(self, label: str, t0_ns: int, t1_ns: int) -> None:
        """Close the stage `label` at t1_ns. It runs from the previous
        boundary, not from t0_ns: the clock reads between two stages
        belong to the later one, so the stages add up to the handler's
        time exactly."""
        self._stages[label] = (
            self._stages.get(label, 0) + t1_ns - self.cursor
        )
        self.cursor = t1_ns
        self._last = label

    def open(self, label: str) -> None:
        """Show the part of stage `label` that starts here in a
        capture: a span the next mark() closes, so the caller marks
        before its next `await` (coroutines interleave on the loop's
        thread, where the profiler nests spans)."""
        if _capturing:
            self._live = open_live(f"call.{label}", self.ids, otel=False)

    def mark(self, label: str) -> None:
        """Close a stage that is a wait between threads or spans an
        `await`: histogram and OTel span, no profiler span (the gap
        between two spans with this call's id is the wait) unless
        open() began one."""
        t1 = time.perf_counter_ns()
        if self._live is not None:
            close_live(self._live)
            self._live = None
        if _debug_spans():
            _interval_span(
                f"call.{label}", self.cursor, t1, self.otel_ctx, self.ids
            )
        self.add(label, t1, t1)

    def served(self, path: str, reason: str = "") -> None:
        self.path, self.reason = path, reason

    def finish(self, t_ns: int) -> str:
        """Observe the stages under the serving path; returns the path
        label. The tail (the return to the handler's exit) folds into
        the last stage."""
        path = self.kind + self.path
        if self._last:
            self._stages[self._last] += t_ns - self.cursor
            self.cursor = t_ns
        for label, wall_ns in self._stages.items():
            child = self._sink.get((path, label))
            if child is not None:
                child.observe(wall_ns * 1e-9)
        return path

    def stages_ns(self) -> Dict[str, int]:
        return dict(self._stages)


class _NoCall:
    """Stands in where a caller hands no record (tests and tools that
    call try_serve or the engine directly): same methods, no series."""

    seq = 0
    ids: dict = {}
    otel_ctx = None

    def add(self, label, t0_ns, t1_ns) -> None:
        pass

    def open(self, label) -> None:
        pass

    def mark(self, label) -> None:
        pass

    def attempt_refused(self) -> None:
        pass

    def served(self, path, reason="") -> None:
        pass


NO_CALL = _NoCall()


def rpc_mark(name: str, ids: dict) -> None:
    """`rpc.begin` / `rpc.end`: the root of a call in a capture. The
    handler is a coroutine, and coroutines interleave on the loop's
    thread where the profiler nests spans, so the root is two short
    marks carrying the call's id rather than one span. A wait that is
    over when it is known (`loop.lag`, `interp.wait`, `flush.queue`)
    is such a mark too, at its end and carrying its length in us."""
    if _capturing:
        with _annotation(name, ids):
            pass


class HostProbes:
    """The two instruments of what no call's own thread times, at 100
    Hz while a daemon serves (docs/monitoring.md "Tracing the
    pipeline"). `loop.lag`: a timer on the serving event loop that
    re-arms itself and observes how late it ran. `interp.wait`: a
    thread that sleeps with the interpreter lock released and observes
    how far it overslept, which is the timer's slack plus the wait to
    get the lock back. Both land in a /metrics histogram always and,
    while a capture runs, as a mark carrying the wait in us."""

    PERIOD_S = 0.010

    def __init__(self, loop_lag, interpreter_wait):
        self._loop_lag = loop_lag
        self._interp_wait = interpreter_wait
        self._loop = None
        self._timer = None
        self._due = 0.0
        self._thread = None
        self._running = False

    def start(self, loop) -> None:
        """Called on `loop`'s own thread."""
        self._loop = loop
        self._running = True
        self._arm()
        self._thread = threading.Thread(
            target=self._sleeper, daemon=True, name="interp-probe"
        )
        self._thread.start()

    def stop(self) -> None:
        """Called on the loop's thread, so it waits for nothing: the
        timer is cancelled and the sleeper ends by itself within one
        period (join() waits for that)."""
        self._running = False
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join(timeout=1.0)
            self._thread = None

    def _arm(self) -> None:
        self._due = time.perf_counter() + self.PERIOD_S
        self._timer = self._loop.call_later(self.PERIOD_S, self._fired)

    def _fired(self) -> None:
        lag = max(time.perf_counter() - self._due, 0.0)
        self._loop_lag.observe(lag)
        rpc_mark("loop.lag", {"lag_us": int(lag * 1e6)})
        if self._running:
            self._arm()

    def _sleeper(self) -> None:
        period = self.PERIOD_S
        while self._running:
            due = time.perf_counter() + period
            time.sleep(period)
            wait = max(time.perf_counter() - due, 0.0)
            self._interp_wait.observe(wait)
            rpc_mark("interp.wait", {"wait_us": int(wait * 1e6)})
