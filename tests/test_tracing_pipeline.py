"""Batch-aware tracing through the async engine pipeline: request spans
link to the flush span that served them (and back) across the batch
boundary, the completion stage runs under the ticket's dispatch-time
context (thread-crossing parentage), exemplars render only under
OpenMetrics negotiation, and trace context rides the TransferSnapshots
payload.

Runs against the real opentelemetry-sdk in-memory exporter when the SDK
wheel is installed; otherwise against a minimal recording
TracerProvider built on the public OTel *API* ABCs (the API ships in
the image, the SDK may not — skipping entirely would leave the whole
tentpole unverified). Skips only when even the API is absent, like the
TLS tests skip without `cryptography`.
"""

import contextlib
import itertools
import random
import threading
import time

import pytest

otel_trace = pytest.importorskip(
    "opentelemetry.trace", reason="opentelemetry API not installed"
)

from gubernator_tpu.api.types import RateLimitReq  # noqa: E402
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig  # noqa: E402
from gubernator_tpu.utils import tracing  # noqa: E402

NOW = 1_753_700_000_000


def mk(key="k", **kw):
    kw.setdefault("name", "t")
    kw.setdefault("duration", 60_000)
    kw.setdefault("limit", 1_000_000)
    kw.setdefault("hits", 1)
    return RateLimitReq(unique_key=key, **kw)


# ---------------------------------------------------------------------------
# recording tracer provider: real SDK when available, API-level fallback


class _Link:
    __slots__ = ("context",)

    def __init__(self, context):
        self.context = context


class _RecSpan(otel_trace.Span):
    def __init__(self, name, context, parent, on_end):
        self.name = name
        self._context = context
        self.parent = parent  # SpanContext or None
        self.attributes = {}
        self.links = []
        self.events = []
        self.status = None
        self._ended = False
        self._on_end = on_end
        self._lock = threading.Lock()

    def end(self, end_time=None):
        with self._lock:
            if self._ended:
                return
            self._ended = True
        self._on_end(self)

    def get_span_context(self):
        return self._context

    def set_attributes(self, attributes):
        self.attributes.update(attributes)

    def set_attribute(self, key, value):
        self.attributes[key] = value

    def add_event(self, name, attributes=None, timestamp=None):
        self.events.append((name, dict(attributes or {})))

    def add_link(self, context, attributes=None):
        self.links.append(_Link(context))

    def update_name(self, name):
        self.name = name

    def is_recording(self):
        return not self._ended

    def set_status(self, status, description=None):
        self.status = status

    def record_exception(self, exception, attributes=None, timestamp=None,
                         escaped=False):
        self.events.append(("exception", {"type": type(exception).__name__}))


class _RecTracer(otel_trace.Tracer):
    def __init__(self, provider):
        self._p = provider

    def start_span(self, name, context=None, kind=otel_trace.SpanKind.INTERNAL,
                   attributes=None, links=None, start_time=None,
                   record_exception=True, set_status_on_exception=True):
        if not self._p.enabled:
            # Disabled outside this module's fixtures so later test
            # modules' daemons see the pre-SDK no-op behavior (a live
            # recorder would start injecting trace metadata into
            # forwarded items suite-wide).
            return otel_trace.INVALID_SPAN
        parent = otel_trace.get_current_span(context).get_span_context()
        if parent is None or not parent.is_valid:
            parent = None
            trace_id = self._p.next_trace_id()
        else:
            trace_id = parent.trace_id
        ctx = otel_trace.SpanContext(
            trace_id=trace_id,
            span_id=self._p.next_span_id(),
            is_remote=False,
            trace_flags=otel_trace.TraceFlags(otel_trace.TraceFlags.SAMPLED),
        )
        span = _RecSpan(name, ctx, parent, self._p._record)
        for k, v in (attributes or {}).items():
            span.set_attribute(k, v)
        for ln in links or ():
            span.add_link(ln.context if hasattr(ln, "context") else ln)
        return span

    @contextlib.contextmanager
    def start_as_current_span(self, name, context=None,
                              kind=otel_trace.SpanKind.INTERNAL,
                              attributes=None, links=None, start_time=None,
                              record_exception=True,
                              set_status_on_exception=True,
                              end_on_exit=True):
        span = self.start_span(
            name, context=context, kind=kind, attributes=attributes,
            links=links,
        )
        with otel_trace.use_span(span, end_on_exit=end_on_exit):
            yield span


class _RecProvider(otel_trace.TracerProvider):
    def __init__(self):
        self.finished = []
        self.enabled = False
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._rng = random.Random(0xC0FFEE)

    def get_tracer(self, *a, **kw):
        return _RecTracer(self)

    def next_span_id(self):
        with self._lock:
            return next(self._ids)

    def next_trace_id(self):
        with self._lock:
            return self._rng.getrandbits(128) or 1

    def _record(self, span):
        with self._lock:
            self.finished.append(span)

    # test surface (mirrors InMemorySpanExporter)
    def get_finished_spans(self):
        with self._lock:
            return list(self.finished)

    def clear(self):
        with self._lock:
            self.finished.clear()


_INSTALLED = {}


def _install_recorder():
    """Install a recording provider exactly once per process (the OTel
    API rejects provider overrides). Prefers the real SDK + in-memory
    exporter; falls back to the API-level recorder above. Returns
    (get_finished, clear, set_enabled)."""
    if _INSTALLED:
        return _INSTALLED["get"], _INSTALLED["clear"], _INSTALLED["enable"]
    try:
        from opentelemetry.sdk.trace import TracerProvider as SdkProvider
        from opentelemetry.sdk.trace.export import SimpleSpanProcessor
        from opentelemetry.sdk.trace.export.in_memory_span_exporter import (
            InMemorySpanExporter,
        )

        exporter = InMemorySpanExporter()
        provider = SdkProvider()
        provider.add_span_processor(SimpleSpanProcessor(exporter))
        otel_trace.set_tracer_provider(provider)
        _INSTALLED["get"] = exporter.get_finished_spans
        _INSTALLED["clear"] = exporter.clear
        _INSTALLED["enable"] = lambda on: None  # SDK records for the session
    except ImportError:
        provider = _RecProvider()
        otel_trace.set_tracer_provider(provider)
        _INSTALLED["get"] = provider.get_finished_spans
        _INSTALLED["clear"] = provider.clear

        def enable(on):
            provider.enabled = on

        _INSTALLED["enable"] = enable
    return _INSTALLED["get"], _INSTALLED["clear"], _INSTALLED["enable"]


@pytest.fixture()
def spans():
    get, clear, enable = _install_recorder()
    tracing.set_trace_level("DEBUG")  # engine flush spans are DEBUG-level
    enable(True)
    clear()
    try:
        yield get
    finally:
        tracing.set_trace_level("INFO")
        enable(False)
        clear()


def _by_name(spanlist, name):
    return [s for s in spanlist if s.name == name]


def _link_contexts(span):
    return {(ln.context.trace_id, ln.context.span_id) for ln in span.links}


def _ctx_key(span):
    sc = span.get_span_context()
    return (sc.trace_id, sc.span_id)


def _parent_key(span):
    p = span.parent
    return (p.trace_id, p.span_id) if p is not None else None


# ---------------------------------------------------------------------------
# object path, pipelined (GUBER_PIPELINE_DEPTH=2)


@pytest.fixture()
def engine():
    eng = DeviceEngine(
        EngineConfig(
            num_groups=1 << 10, batch_size=64, batch_wait_s=0.0005,
            pipeline_depth=2,
        ),
        now_fn=lambda: NOW,
    )
    yield eng
    eng.close()


def test_request_flush_linkage_and_parentage_object_path(engine, spans):
    with tracing.span("test.request", level="INFO") as req_span:
        for r in engine.check_batch([mk(f"lk{i}") for i in range(6)]):
            assert not r.error
    done = spans()
    flushes = _by_name(done, "engine.flush")
    assert flushes, [s.name for s in done]
    # flush span attributes: batch-aware identity
    by_seq = {}
    for f in flushes:
        assert f.attributes["path"] == "object"
        assert f.attributes["pipeline_depth"] == 2
        assert f.attributes["ticket_seq"] >= 1
        assert f.attributes["waves"] >= 1
        by_seq[f.attributes["ticket_seq"]] = f
    # the request span links to the flush span(s) that served it...
    req = _by_name(done, "test.request")[0]
    flush_ctxs = {_ctx_key(f) for f in flushes}
    assert _link_contexts(req) & flush_ctxs, (
        "request span carries no link to any flush span"
    )
    # ...and the flush span links back to the request span
    req_ctx = _ctx_key(req)
    assert any(req_ctx in _link_contexts(f) for f in flushes)
    # completion stage: engine.complete is a CHILD of its flush span
    # even though it ran on the completion thread (the ticket carried
    # the dispatch-time context across the boundary)
    completes = _by_name(done, "engine.complete")
    assert completes
    for c in completes:
        pk = _parent_key(c)
        assert pk in flush_ctxs, "completion span not parented to a flush"
        assert c.attributes["ticket_seq"] == by_seq[
            c.attributes["ticket_seq"]
        ].attributes["ticket_seq"]
    # flush span duration covers completion: it ended AFTER its
    # engine.complete child was recorded (finished list is end-ordered)
    first_flush = flushes[0]
    order = [id(s) for s in done]
    for c in completes:
        if _parent_key(c) == _ctx_key(first_flush):
            assert order.index(id(c)) < order.index(id(first_flush))


def test_ticket_seq_monotonic_and_recorder_join_key(engine, spans):
    engine.check_batch([mk("jk1")])
    engine.check_batch([mk("jk2")])
    done = spans()
    flushes = _by_name(done, "engine.flush")
    seqs = sorted(f.attributes["ticket_seq"] for f in flushes)
    assert seqs == sorted(set(seqs)), "ticket seqs must be unique"
    # the flight recorder's trace_id matches a recorded flush span's
    recs = [
        r for r in engine.metrics.recorder.snapshot()
        if r.get("path") == "object" and r.get("trace_id")
    ]
    assert recs, "recorder records carry no trace_id join key"
    flush_tids = {
        format(f.get_span_context().trace_id, "032x") for f in flushes
    }
    for r in recs:
        assert r["trace_id"] in flush_tids
        assert r["ticket"] in seqs


def test_columnar_path_parentage(engine, spans):
    from gubernator_tpu import wire

    if not wire.available():
        pytest.skip("native wire parser unavailable")
    from gubernator_tpu.service import pb

    msg = pb.pb.GetRateLimitsReq()
    for i in range(5):
        msg.requests.append(pb.req_to_pb(mk(f"col{i}")))
    cols = wire.parse_requests(msg.SerializeToString())
    with tracing.span("test.columnar_request", level="INFO") as req_span:
        out = engine.check_columns(cols, now=NOW)
    assert out is not None
    done = spans()
    req = _by_name(done, "test.columnar_request")[0]
    flushes = [
        f for f in _by_name(done, "engine.flush")
        if f.attributes.get("path") == "columnar"
    ]
    assert flushes
    # synchronous path: direct parent-child, no links needed
    assert _parent_key(flushes[0]) == _ctx_key(req)


def test_failed_ticket_lands_under_flush_trace(spans):
    eng = DeviceEngine(
        EngineConfig(
            num_groups=1 << 10, batch_size=64, batch_wait_s=0.0005,
            pipeline_depth=2,
        ),
        now_fn=lambda: NOW,
    )
    try:
        boom = RuntimeError("injected completion failure")
        orig = eng._complete

        def failing(t):
            raise boom

        eng._complete = failing
        resp = eng.check_async(mk("fail")).result(timeout=10)
        assert "injected completion failure" in resp.error
        eng._complete = orig
        # The failed future resolves INSIDE the ticket_failed span (the
        # caller unblocks before recovery runs), so the span may not
        # have ended yet when .result() returns — wait for the export.
        deadline = time.monotonic() + 5.0
        while True:
            done = spans()
            failed = _by_name(done, "engine.ticket_failed")
            if failed or time.monotonic() > deadline:
                break
            time.sleep(0.01)
        assert failed
        flushes = _by_name(done, "engine.flush")
        flush_ctxs = {_ctx_key(f) for f in flushes}
        assert _parent_key(failed[0]) in flush_ctxs
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# exemplars: OpenMetrics negotiation only


def test_exemplars_render_only_under_openmetrics(engine, spans):
    from gubernator_tpu.metrics import (
        Metrics, OPENMETRICS_CONTENT_TYPE, wire_engine_telemetry,
    )

    m = Metrics()
    wire_engine_telemetry(m, engine)
    engine.check_batch([mk(f"ex{i}") for i in range(4)])
    plain = m.render().decode()
    assert "# {trace_id=" not in plain, "plain exposition must stay clean"
    assert not plain.rstrip().endswith("# EOF")
    om = m.render(openmetrics=True).decode()
    assert '# {trace_id="' in om
    assert om.rstrip().endswith("# EOF")
    # the exemplar's trace id is a real recorded flush trace
    tid = om.split('# {trace_id="', 1)[1].split('"', 1)[0]
    flush_tids = {
        format(f.get_span_context().trace_id, "032x")
        for f in _by_name(spans(), "engine.flush")
    }
    assert tid in flush_tids
    # and the negotiated entry point picks the right body per Accept
    body, ctype = m.render_negotiated("application/openmetrics-text")
    assert ctype == OPENMETRICS_CONTENT_TYPE
    assert b"# {trace_id=" in body
    body2, ctype2 = m.render_negotiated("text/plain")
    assert b"# {trace_id=" not in body2


def test_exemplars_knob_off():
    from gubernator_tpu.metrics import Metrics, wire_engine_telemetry

    _get, _clear, enable = _install_recorder()
    tracing.set_trace_level("DEBUG")
    enable(True)
    try:
        eng = DeviceEngine(
            EngineConfig(
                num_groups=1 << 10, batch_size=64, batch_wait_s=0.0005,
                exemplars=False,
            ),
            now_fn=lambda: NOW,
        )
        try:
            m = Metrics()
            wire_engine_telemetry(m, eng)
            eng.check_batch([mk("exoff")])
            om = m.render(openmetrics=True).decode()
            assert "# {trace_id=" not in om
        finally:
            eng.close()
    finally:
        tracing.set_trace_level("INFO")
        enable(False)


# ---------------------------------------------------------------------------
# trace context rides the GLOBAL + handover carriers


def test_propagate_inject_rides_handover_payload(spans):
    from gubernator_tpu.service import pb
    from gubernator_tpu.store.store import ItemSnapshot

    snap = ItemSnapshot(
        key="t_h1", algorithm=0, status=0, limit=10, duration=60_000,
        remaining=9, stamp=NOW, expire_at=NOW + 60_000, burst=0,
    )
    with tracing.span("test.handover", level="INFO") as s:
        payload = pb.snapshots_to_bytes(
            [snap], metadata=tracing.propagate_inject({})
        )
        want_tid = format(s.get_span_context().trace_id, "032x")
    snaps, md = pb.snapshots_md_from_bytes(payload)
    assert len(snaps) == 1 and snaps[0].key == "t_h1"
    assert "traceparent" in md
    assert want_tid in md["traceparent"]
    # receiver half: extract + attach restores the sender's trace
    ctx = tracing.propagate_extract(md)
    assert ctx is not None
    with tracing.attached(ctx):
        got = otel_trace.get_current_span().get_span_context()
        assert format(got.trace_id, "032x") == want_tid
    # payloads without the md field stay decodable (wire back-compat)
    legacy = pb.snapshots_to_bytes([snap])
    snaps2, md2 = pb.snapshots_md_from_bytes(legacy)
    assert len(snaps2) == 1 and md2 == {}
    assert pb.snapshots_from_bytes(legacy)[0].key == "t_h1"


def test_no_sdk_path_attaches_nothing(engine):
    # With the trace level back at INFO, flush spans (DEBUG) are never
    # created: tickets carry no span/context and responses carry no
    # trace metadata — the knob-off serving path stays dark.
    tracing.set_trace_level("INFO")
    resp = engine.check_async(mk("dark")).result(timeout=10)
    assert not resp.error
    recs = engine.metrics.recorder.snapshot()
    assert recs[-1].get("trace_id") == ""


# ---------------------------------------------------------------------------
# tracing.stage(): the OTel reader of a call's timeline


def test_stage_and_mark_open_debug_children_of_the_request_span(spans):
    rec = tracing.CallRecord({})
    root = tracing.start_span("rpc.GetRateLimits", level="INFO", call=rec.seq)
    rec.otel_ctx = tracing.context_of(root)
    rec.begin(time.perf_counter_ns())
    done = threading.Event()

    def executor_thread():  # as fastpath.try_serve runs: another thread
        with tracing.attached(rec.otel_ctx):
            rec.mark("executor_wait")
            with tracing.stage("call.parse", rec, rec.ids):
                with tracing.stage("flush.hash", rec, {"flush": 9, **rec.ids}):
                    pass
        done.set()

    threading.Thread(target=executor_thread).start()
    assert done.wait(10)
    tracing.end_span(root)
    got = {s.name: s for s in spans()}
    assert {"rpc.GetRateLimits", "call.executor_wait", "call.parse",
            "flush.hash"} <= set(got)
    root_key = _ctx_key(got["rpc.GetRateLimits"])
    assert _parent_key(got["call.executor_wait"]) == root_key
    assert _parent_key(got["call.parse"]) == root_key
    assert _parent_key(got["flush.hash"]) == _ctx_key(got["call.parse"])
    assert got["flush.hash"].attributes["flush"] == 9
    assert got["call.parse"].attributes["call"] == rec.seq
    assert set(rec.stages_ns()) == {"executor_wait", "parse", "hash"}


def test_stage_opens_no_span_at_info(spans):
    tracing.set_trace_level("INFO")
    rec = tracing.CallRecord({})
    rec.begin(time.perf_counter_ns())
    rec.mark("executor_wait")
    with tracing.stage("call.parse", rec, rec.ids):
        pass
    assert [s.name for s in spans()] == []
    assert set(rec.stages_ns()) == {"executor_wait", "parse"}
