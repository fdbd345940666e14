"""tools/profile_gaps.py and the spans no call owns (ISSUE 38): a capture
taken while a daemon serves holds `loop.lag`, `interp.wait`,
`complete.idle`, `flush.queue` and `call.route` in plane /host:CPU; the
tool lays a mark that carries its wait back over the wait; and its rule
gives a gap to `queue`, `route`, `tick.*`, `interp.wait`, `loop.lag` and
`complete.idle` under their own names instead of "unattributed", the
names still adding up to the plane's idle time (hand-made timelines)."""

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
pytestmark = pytest.mark.filterwarnings("ignore:builtin type:DeprecationWarning")


# ---- the rule, on hand-made timelines ---------------------------------------


def test_a_pump_flushs_gap_goes_to_route_and_queue():
    # an object-path call: routed 2..3, its entry queued 3..6, the pump's
    # flush 9 launches the program that starts at 10
    spans = [
        (1.0, 1.0, "rpc.begin", 4, 0),
        (1.0, 2.0, "call.pb_decode", 4, 0),
        (2.0, 3.0, "call.route", 4, 0),
        (3.0, 6.0, "flush.queue", 4, 9),
        (6.0, 7.0, "flush.hash", 4, 9),
        (7.0, 8.0, "flush.lock_wait", 4, 9),
        (8.0, 9.5, "flush.dispatch", 4, 9),
    ]
    got = profile_gaps.attribute_plane([(10.0, 11.0)], spans, 0.0, 11.0)
    assert got == pytest.approx({
        profile_gaps.NOT_YET: 1.0,
        "route": 1.0, "queue": 3.0, "hash": 1.0, "lock_wait": 1.0,
        "dispatch": 1.5,
        profile_gaps.UNATTRIBUTED: 1.0 + 0.5,  # pb_decode; after the launch
    })
    assert sum(got.values()) == pytest.approx(10.0)


def test_what_no_call_owns_is_named_in_its_order():
    # flush 3 dispatches 18..19.5 and its program starts at 20; before it
    # the tick held the lock, the probes overslept and nothing was in flight
    spans = [
        (18.0, 19.5, "flush.dispatch", 1, 3),
        (17.0, 18.0, "flush.lock_wait", 1, 3),
        (2.0, 4.0, "tick.lock_wait", 0, 0),
        (4.0, 5.0, "tick.launch", 0, 0),
        (5.0, 8.0, "tick.read", 0, 0),
        (7.0, 10.0, "interp.wait", 0, 0),   # 7..8 is the tick's read first
        (9.0, 12.0, "loop.lag", 0, 0),      # 9..10 is interp.wait first
        (0.0, 16.0, "complete.idle", 0, 0),  # what is left of 0..16
    ]
    got = profile_gaps.attribute_plane([(20.0, 21.0)], spans, 0.0, 21.0)
    assert got == pytest.approx({
        "tick.lock_wait": 2.0, "tick.launch": 1.0, "tick.read": 3.0,
        "interp.wait": 2.0, "loop.lag": 2.0,
        "complete.idle": 2.0 + 4.0,          # 0..2 and 12..16
        "lock_wait": 1.0, "dispatch": 1.5,
        profile_gaps.UNATTRIBUTED: 1.0 + 0.5,  # 16..17; after the launch
    })
    assert sum(got.values()) == pytest.approx(20.0)


def test_a_gap_no_flush_launched_still_goes_to_what_no_call_owns():
    # the capture's tail: no program ends the gap, so no flush divides it
    spans = [(6.5, 7.5, "loop.lag", 0, 0), (7.0, 9.0, "complete.idle", 0, 0)]
    got = profile_gaps.attribute_plane([(5.0, 6.0)], spans, 5.0, 10.0)
    assert got == pytest.approx({
        "loop.lag": 1.0, "complete.idle": 1.5,
        profile_gaps.UNATTRIBUTED: 0.5 + 1.0,
    })
    assert sum(got.values()) == pytest.approx(4.0)


def test_every_name_the_rule_can_give_is_printed():
    given = set(profile_gaps.STAGES) | set(profile_gaps.GLOBAL) | set(
        profile_gaps.OTHER.values()) | {
        profile_gaps.NOT_YET, profile_gaps.UNATTRIBUTED}
    assert given == set(profile_gaps.ORDER)
    assert len(profile_gaps.ORDER) == len(set(profile_gaps.ORDER))
    assert set(profile_gaps.WAIT_US) == {"flush.queue", "interp.wait",
                                         "loop.lag"}


def test_a_store_waves_gap_goes_to_its_stage_and_its_programs_are_named():
    """ISSUE 41: inside `dispatch` a Store wave's `readthrough` and
    `store_rows` take the gap first, and the sequence's device programs
    are counted under the names a capture gives them, on one chip and
    on a mesh, with the mesh programs' named phases beside them."""
    spans = [
        (0.0, 0.0, "rpc.begin", 1, 0), (1.0, 9.0, "call.engine", 1, 0),
        (2.0, 8.0, "flush.dispatch", 1, 5),
        (2.0, 4.0, "flush.readthrough", 1, 5),
        (5.0, 7.0, "flush.store_rows", 1, 5),
    ]
    got = profile_gaps.attribute_plane([(8.0, 9.0)], spans, 2.0, 9.0)
    assert got == pytest.approx({
        "readthrough": 2.0, "store_rows": 2.0, "dispatch": 1.0 + 1.0})
    named = [("jit_probe_exists_fn(1)", 2e-5), ("jit_decide_fn(2)", 2e-4),
             ("jit_gather_rows_fn(3)", 3e-5), ("jit_gather_rows_fused(4)", 1e-5),
             ("jit_sync_fn(5)", 1e-3)]
    assert profile_gaps.store_programs(named) == {
        "probe_exists": (1, 2e-5), "decide": (1, 2e-4),
        "gather_rows": (2, pytest.approx(4e-5))}
    assert profile_gaps.STORE_PHASES["gather_rows"] == (
        "owner_mask", "store_rows_local", "psum_rows")
    assert profile_gaps.STORE_PHASES["probe_exists"] == (
        "owner_mask", "probe_local", "psum_probe")


# ---- a capture of a serving daemon ------------------------------------------


def body(keys, slow=False) -> bytes:
    """`slow`: an item carries metadata, so the call needs the object
    path (fastpath's reason `slow_item`)."""
    msg = pb.pb.GetRateLimitsReq()
    for k in keys:
        r = msg.requests.add()
        r.name, r.unique_key = "gaps", k
        r.hits, r.limit, r.duration = 1, 1_000_000, 60_000
    if slow:
        msg.requests[-1].metadata["tenant"] = "t"
    return msg.SerializeToString()


@pytest.fixture(scope="module")
def capture(loop_thread, tmp_path_factory):
    """The host spans (profile_gaps.read_trace) of a 3 s capture taken
    while calls are served: most columnar, one in five with an item
    that carries metadata, served by the object path through the pump."""
    root = str(tmp_path_factory.mktemp("profiles"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(profiler, "trace_root", lambda: root)
        d = loop_thread.run(
            Daemon.spawn(DaemonConfig(cache_size=4096)), timeout=120)
        try:
            reply = {}

            def profile():
                r = requests.get(
                    f"http://{d.http_address}/debug/profile?seconds=3",
                    timeout=60)
                r.raise_for_status()
                reply.update(r.json())

            t = threading.Thread(target=profile)
            t.start()
            with grpc.insecure_channel(d.grpc_address) as ch:
                call = ch.unary_unary(V1, request_serializer=None,
                                      response_deserializer=None)
                for _ in range(400):
                    if tracing.capturing():
                        break
                    call(body(["warm"]), timeout=30)
                for i in range(40):
                    call(body([f"a{i}", f"b{i}"], slow=i % 5 == 4),
                         timeout=30)
            t.join(60)
            assert reply.get("trace_dir"), reply
            _, spans = profile_gaps.read_trace(
                profile_gaps.find_trace(reply["trace_dir"]))
        finally:
            loop_thread.run(d.close())
    return spans


def test_the_capture_holds_the_spans_no_call_owns(capture):
    names = {n for _, _, n, _, _ in capture}
    assert {"loop.lag", "interp.wait", "complete.idle"} <= names
    assert {"flush.queue", "call.route"} <= names  # the object-path calls
    assert {"rpc.begin", "call.parse", "flush.dispatch"} <= names
    # 100 firings a second over 3 s, less what the capture's edges cut
    assert sum(1 for s in capture if s[2] == "loop.lag") >= 100
    assert sum(1 for s in capture if s[2] == "interp.wait") >= 100


def test_a_mark_is_laid_back_over_its_wait(capture):
    for a, b, name, call, flush in capture:
        if name in profile_gaps.WAIT_US:
            assert b >= a
        if name == "flush.queue":
            assert flush > 0  # carries the flush it waited for
    # a pump flush's queue span ends where its flush's first stage begins
    firsts = {}
    for a, _, name, _, flush in capture:
        if name == "flush.hash":
            firsts[flush] = min(a, firsts.get(flush, a))
    ends = {fl: b for _, b, n, _, fl in capture if n == "flush.queue"}
    assert ends and set(ends) <= set(firsts)
    for fl, b in ends.items():
        assert b <= firsts[fl] + 1e-3


def test_route_spans_hold_no_await(capture):
    # call.route is open on the loop's thread from the handler's entry
    # into the service to its first await: no other call's mark inside it
    routes = [(a, b, call) for a, b, n, call, _ in capture if n == "call.route"]
    assert routes
    marks = [(a, call) for a, _, n, call, _ in capture
             if n in ("rpc.begin", "rpc.end")]
    for a, b, call in routes:
        assert not [c for t, c in marks if a < t < b and c != call]
