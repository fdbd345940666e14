"""The program's own spans on the profiler's timeline: a /debug/profile
capture holds rpc.* marks and call.* / flush.* spans in plane /host:CPU,
nested per thread and joined by ids; the capture traces no Python unless
asked; with no capture running the stage helper builds no annotation.
tools/profile_gaps.py's rule is checked on a hand-made timeline."""

import threading

import grpc
import pytest
import requests

from gubernator_tpu.service import pb, profiler
from gubernator_tpu.service.config import DaemonConfig
from gubernator_tpu.service.daemon import Daemon
from gubernator_tpu.utils import tracing
from tools import profile_gaps

V1 = "/pb.gubernator.V1/GetRateLimits"
CALLS = 50
OWN = ("rpc.", "call.", "flush.")
# reading an event's stats warns once an event in this JAX
pytestmark = pytest.mark.filterwarnings("ignore:builtin type:DeprecationWarning")


@pytest.fixture(scope="module")
def daemon(loop_thread, tmp_path_factory):
    # a CPU capture is tens of MB: keep them with the test's own files
    root = str(tmp_path_factory.mktemp("profiles"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(profiler, "trace_root", lambda: root)
        d = loop_thread.run(
            Daemon.spawn(DaemonConfig(cache_size=4096)), timeout=120)
        yield d
        loop_thread.run(d.close())


def body(keys, slow=False) -> bytes:
    """`slow`: an item carries metadata, so the call needs the object
    path (fastpath's reason `slow_item`)."""
    msg = pb.pb.GetRateLimitsReq()
    for k in keys:
        r = msg.requests.add()
        r.name, r.unique_key = "prof", k
        r.hits, r.limit, r.duration = 1, 1_000_000, 60_000
    if slow:
        msg.requests[-1].metadata["tenant"] = "t"
    return msg.SerializeToString()


def capture_while_serving(daemon, query: str) -> tuple:
    """(/debug/profile's reply, host spans) of a capture taken while
    CALLS calls are served: most columnar, one in ten with an item
    that carries metadata, served by the object path."""
    started = threading.Event()
    reply = {}

    def profile():
        started.set()
        r = requests.get(
            f"http://{daemon.http_address}/debug/profile?seconds=4{query}",
            timeout=60,
        )
        r.raise_for_status()
        reply.update(r.json())

    t = threading.Thread(target=profile)
    t.start()
    started.wait(10)
    with grpc.insecure_channel(daemon.grpc_address) as ch:
        call = ch.unary_unary(V1, request_serializer=None,
                              response_deserializer=None)
        # wait until the capture runs, then serve inside it
        for _ in range(200):
            if tracing.capturing():
                break
            call(body(["warm"]), timeout=30)
        for i in range(CALLS):
            call(body([f"a{i}", f"b{i}"], slow=i % 10 == 9), timeout=30)
    t.join(60)
    assert not t.is_alive() and "trace_dir" in reply, reply
    return reply, read_host_plane(reply["trace_dir"])


def read_host_plane(trace_dir: str) -> dict:
    """{line index: [(start_ns, end_ns, name, stats)]} of /host:CPU."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(profile_gaps.find_trace(trace_dir))
    lines = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            lines[i] = [
                (e.start_ns, e.start_ns + e.duration_ns, e.name,
                 dict(e.stats) if e.name.startswith(OWN) else {})
                for e in line.events
            ]
    return lines


@pytest.fixture(scope="module")
def default_capture(daemon):
    return capture_while_serving(daemon, "")


def own(lines: dict) -> list:
    return [e for evs in lines.values() for e in evs
            if e[2].startswith(OWN)]


def test_capture_holds_the_catalog_in_the_host_plane(default_capture):
    reply, lines = default_capture
    names = {e[2] for e in own(lines)}
    assert {"rpc.begin", "rpc.end"} <= names
    assert {"call.parse", "call.engine", "call.build",  # columnar
            "call.pb_decode", "call.pb_encode"} <= names  # object
    assert {"flush.hash", "flush.waves", "flush.keydict", "flush.lock_wait",
            "flush.dispatch", "flush.readback", "flush.post"} <= names
    assert reply["python"] is False
    assert reply["start_s"] >= 0 and reply["stop_s"] >= 0


def test_spans_nest_on_their_threads_line(default_capture):
    _, lines = default_capture
    for evs in lines.values():
        stack = []
        for a, b, name, _ in sorted(
            (e for e in evs if e[2].startswith(("call.", "flush."))),
            key=lambda e: (e[0], -e[1]),
        ):
            while stack and stack[-1][1] <= a:
                stack.pop()
            if stack:  # inside the enclosing span, never across its end
                assert b <= stack[-1][1], (name, stack[-1][2])
                # a flush stage nests in its call's engine stage
                if name.startswith("flush.") and stack[-1][2].startswith("call."):
                    assert stack[-1][2] == "call.engine"
            stack.append((a, b, name))


def test_spans_share_call_and_flush_ids(default_capture):
    _, lines = default_capture
    spans = own(lines)
    begun = {e[3]["call"] for e in spans if e[2] == "rpc.begin"}
    ended = {e[3]["call"] for e in spans if e[2] == "rpc.end"}
    assert len(begun & ended) >= CALLS // 2  # the rest outlasted the capture
    by_flush = {}
    for _, _, name, st in spans:
        if name.startswith("flush."):
            assert st["flush"] > 0 and "call" in st, name
            by_flush.setdefault(st["flush"], set()).add((name, st["call"]))
        elif name.startswith("call."):
            assert st["call"] in begun | ended or st["call"] > 0
    whole = [v for v in by_flush.values()
             if {n for n, _ in v} >= {"flush.hash", "flush.dispatch",
                                      "flush.readback"}]
    assert len(whole) >= CALLS // 2 - 5
    for v in whole:  # one flush, one call id on every stage
        assert len({c for _, c in v}) == 1
    # the dispatch span says how many waves it launched
    assert all("waves" in e[3] for e in spans if e[2] == "flush.dispatch")


def test_no_python_frames_unless_asked(daemon, default_capture):
    _, lines = default_capture
    assert not [e for evs in lines.values() for e in evs if e[2].startswith("$")]
    reply, lines = capture_while_serving(daemon, "&python=1")
    assert reply["python"] is True
    assert [e for evs in lines.values() for e in evs if e[2].startswith("$")]
    assert {"rpc.begin", "call.engine", "flush.dispatch"} <= {
        e[2] for e in own(lines)}


def test_no_annotation_is_built_without_a_capture(monkeypatch):
    import jax

    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **k):
            built.append(a)
            super().__init__(*a, **k)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)

    class Sink:
        def __init__(self):
            self.got = []

        def add(self, label, t0, t1):
            self.got.append((label, t1 >= t0))

    sink = Sink()
    assert not tracing.capturing()
    with tracing.stage("flush.hash", sink, {"flush": 1, "call": 1}):
        pass
    tracing.rpc_mark("rpc.begin", {"call": 1})
    assert tracing.open_live("flush.dispatch", {"flush": 1}) is None
    assert built == [] and sink.got == [("hash", True)]
    monkeypatch.setattr(tracing, "_capturing", True)
    with tracing.stage("flush.hash", sink, {"flush": 1, "call": 1}):
        pass
    tracing.rpc_mark("rpc.begin", {"call": 1})
    assert [a[0] for a in built] == ["flush.hash", "rpc.begin"]


# ---- tools/profile_gaps.py: the rule, on a hand-made timeline ---------------


def test_gap_is_divided_along_the_launching_flushs_call():
    # one device program at 10..11 and one at 20..21; window 0..22
    programs = [(10.0, 11.0), (20.0, 21.0)]
    spans = [
        # call 1 -> flush 7 launches the first program
        (2.0, 2.0, "rpc.begin", 1, 0),
        (3.0, 4.0, "call.parse", 1, 0),
        (4.0, 10.5, "call.engine", 1, 0),
        (4.0, 5.0, "flush.hash", 1, 7),
        (5.0, 7.0, "flush.waves", 1, 7),
        (7.0, 8.0, "flush.lock_wait", 1, 7),
        (8.0, 9.5, "flush.dispatch", 1, 7),
        # call 2 -> flush 8 launches the second; it arrived at 15
        (15.0, 15.0, "rpc.begin", 2, 0),
        (15.0, 16.0, "call.parse", 2, 0),
        (16.0, 19.5, "flush.dispatch", 2, 8),
    ]
    got = profile_gaps.attribute_plane(programs, spans, 0.0, 22.0)
    assert got == pytest.approx({
        profile_gaps.NOT_YET: 2.0 + 4.0,       # 0..2 and 11..15
        "executor_wait": 1.0,                  # 2..3 (call 2 has none)
        "parse": 1.0 + 1.0,
        "hash": 1.0, "waves": 2.0, "lock_wait": 1.0,
        "dispatch": 1.5 + 3.5,
        profile_gaps.UNATTRIBUTED: 0.5 + 0.5 + 1.0,  # after each launch; the tail
    })
    idle = 22.0 - 2.0
    assert sum(got.values()) == pytest.approx(idle)


def test_a_wait_for_another_flushs_readback_is_named():
    # flush 8's program starts at 20; its own spans cover 16..19.5 only,
    # and flush 7 was being read from 12 to 15
    spans = [
        (8.0, 9.0, "flush.dispatch", 1, 7),
        (12.0, 15.0, "flush.readback", 1, 7),
        (10.0, 10.0, "rpc.begin", 2, 0),
        (16.0, 19.5, "flush.dispatch", 2, 8),
    ]
    got = profile_gaps.attribute_plane(
        [(9.0, 11.0), (20.0, 21.0)], spans, 9.0, 21.0)
    assert got == pytest.approx({
        "another flush: readback": 3.0, "dispatch": 3.5,
        profile_gaps.UNATTRIBUTED: 1.0 + 1.0 + 0.5,
    })


def test_gap_with_no_flush_before_it_is_unattributed():
    got = profile_gaps.attribute_plane([(5.0, 6.0)], [], 0.0, 6.0)
    assert got == {profile_gaps.UNATTRIBUTED: 5.0}
