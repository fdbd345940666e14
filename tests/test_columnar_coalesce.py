"""Group commit at the entry of `check_columns` (ISSUE 39): small columnar
calls that arrive while another flush is in its host stage share the next
flush. Deterministic throughout: the test holds the engine lock, so the
first call stays in its host stage (it has asked for the lock) while the
others arrive one after another and join the waiting batch; releasing the
lock launches the first, whose launch hands the turn to the batch.

- merged equals serial: the members' answers are those of the same calls
  served one after another in arrival order with their own timestamps
  (token and leaky, shared and distinct keys, with and without a Store,
  on DeviceEngine and on the replica split with GLOBAL lanes);
- a lone call waits for nobody and reads one call a flush;
- K waiting calls are one flush;
- what may not join goes straight through: a call too large to meet a
  peer, NO_BATCHING, `select`;
- a merged assembly above `max_waves` is one flush of several launches
  (ISSUE 44; until then it was refused and every member served alone);
- TableCommittedError reaches every member; any other error sends every
  member to the object path;
- consumption is exact under 32 threads on ten keys.
"""

import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from gubernator_tpu import wire
from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.runtime.engine import (
    DeviceEngine,
    EngineConfig,
    TableCommittedError,
)
from gubernator_tpu.service import fastpath, pb
from gubernator_tpu.store import MemoryStore, attach_store

NOW = 1_753_700_000_000

pytestmark = pytest.mark.skipif(
    not wire.available(), reason="native wirepath unavailable"
)


def mk(key, **kw):
    kw.setdefault("duration", 60_000)
    kw.setdefault("limit", 10)
    kw.setdefault("hits", 1)
    return RateLimitReq(name="gc", unique_key=key, **kw)


def wire_bytes(reqs) -> bytes:
    msg = pb.pb.GetRateLimitsReq()
    for r in reqs:
        msg.requests.append(pb.req_to_pb(r))
    return msg.SerializeToString()


def columns(reqs):
    return wire.parse_requests(wire_bytes(reqs))


def device_engine(**kw):
    kw.setdefault("num_groups", 1 << 8)
    kw.setdefault("ways", 4)
    kw.setdefault("batch_size", 32)
    return DeviceEngine(EngineConfig(**kw), now_fn=lambda: NOW)


def store_engine(**kw):
    eng = device_engine(**kw)
    attach_store(eng, MemoryStore())
    return eng


def ici_engine(**kw):
    from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig

    return IciEngine(
        IciEngineConfig(
            num_groups=256, ways=4, num_slots=512, replica_ways=4,
            batch_size=32, sync_wait_s=3600.0, **kw,
        ),
        now_fn=lambda: NOW,
    )


ENGINES = {"device": device_engine, "store": store_engine, "ici": ici_engine}


def flush_calls(eng):
    """(columnar and pump flushes observed, calls they served)."""
    s = eng.metrics.flush_calls.summary()
    return s["count"], int(s["sum"])


def over_max_waves(eng) -> int:
    return int(eng.metrics.flushes_over_max_waves.labels().get())


def joins(eng) -> int:
    return eng.metrics.stage_duration.label_summaries(qs=()).get(
        ("join",), {"count": 0}
    )["count"]


def wait_for(cond, what: str, timeout_s: float = 30.0):
    end = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < end, f"timed out waiting for {what}"
        time.sleep(0.001)


class Held:
    """Hold the engine lock with `first` (a call's requests) in its host
    stage, let calls join one after another, then release: `results`
    holds each call's outcome in the order it was submitted, as
    ("ok", answer) or ("raised", exception)."""

    def __init__(self, eng, serve=None):
        self.eng = eng
        self.serve = serve or (
            lambda reqs, now: eng.check_columns(columns(reqs), now=now)
        )
        self.threads = []
        self.results = []

    def submit(self, reqs, now=NOW):
        i = len(self.results)
        self.results.append(None)

        def run():
            try:
                self.results[i] = ("ok", self.serve(reqs, now))
            except BaseException as e:  # noqa: BLE001 - the test reads it
                self.results[i] = ("raised", e)

        t = threading.Thread(target=run, daemon=True)
        self.threads.append(t)
        t.start()

    def __enter__(self):
        self.eng._lock.acquire()
        return self

    def first(self, reqs, now=NOW):
        """A call that goes straight through and stops at the lock."""
        self.submit(reqs, now)
        wait_for(lambda: self.eng._gate.active == 1, "the first call")

    def join(self, reqs, now=NOW):
        """A call that must join the waiting batch, after those before."""
        want = len(self.eng._gate.waiting) + 1
        self.submit(reqs, now)
        wait_for(lambda: len(self.eng._gate.waiting) == want, "a joiner")

    def __exit__(self, *exc):
        self.eng._lock.release()
        for t in self.threads:
            t.join(timeout=60)
            assert not t.is_alive(), "a call never returned"
        gate = self.eng._gate
        assert (gate.active, gate.waiting, gate.items) == (0, [], 0)
        return False


def answers(out):
    return [a.tolist() for a in out]


# ---- merged equals serial ---------------------------------------------------


def shared_keys(algo, behavior):
    """Five calls of two items over three keys: every key is hit by
    several members, one of them past its limit of 3."""
    keys = ["a", "b", "a", "c", "a", "b", "a", "a", "c", "b"]
    return [
        [mk(k, limit=3, algorithm=algo, behavior=behavior)
         for k in keys[i:i + 2]]
        for i in range(0, len(keys), 2)
    ]


def distinct_keys(algo, behavior):
    return [
        [mk(f"d{2 * i}", algorithm=algo, behavior=behavior),
         mk(f"d{2 * i + 1}", algorithm=algo, behavior=behavior, hits=2)]
        for i in range(5)
    ]


CASES = [
    (engine, keys.__name__, algo, behavior)
    for engine in ENGINES
    for keys in (shared_keys, distinct_keys)
    for algo in (Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET)
    for behavior in ((0, int(Behavior.GLOBAL)) if engine == "ici" else (0,))
]


@pytest.mark.parametrize("engine,keys,algo,behavior", CASES)
def test_merged_equals_serial(engine, keys, algo, behavior):
    calls = {"shared_keys": shared_keys, "distinct_keys": distinct_keys}[
        keys](algo, behavior)
    blocker = [mk("blocker")]
    merged, serial = ENGINES[engine](), ENGINES[engine]()
    try:
        before = flush_calls(merged)
        with Held(merged) as h:
            h.first(blocker)
            for i, reqs in enumerate(calls):
                h.join(reqs, now=NOW + 1 + i)
        assert flush_calls(merged) == (before[0] + 2, before[1] + 1 + len(calls))
        assert joins(merged) == len(calls)  # each joined call's wait, once
        want = [serial.check_columns(columns(blocker), now=NOW)]
        want += [
            serial.check_columns(columns(reqs), now=NOW + 1 + i)
            for i, reqs in enumerate(calls)
        ]
        for (kind, got), exp in zip(h.results, want):
            assert kind == "ok" and answers(got) == answers(exp)
        # ... and the two tables agree afterwards
        probe = [mk(k, hits=0, limit=3, algorithm=algo, behavior=behavior)
                 for k in ("a", "b", "c")]
        assert answers(merged.check_columns(columns(probe), now=NOW + 9)) == \
            answers(serial.check_columns(columns(probe), now=NOW + 9))
        rec = [r for r in merged.metrics.recorder.snapshot()
               if r.get("calls", 1) > 1]
        assert [r["calls"] for r in rec] == [len(calls)] and rec[0]["n"] == 10
    finally:
        merged.close()
        serial.close()


def test_a_members_own_now_rides_its_lanes_and_a_carried_stamp_stays():
    """A token bucket's reset_time is created_at + duration: each
    member's comes from its own `now`, an item that carried a
    created_at keeps it."""
    eng = device_engine()
    try:
        with Held(eng) as h:
            h.first([mk("blocker")])
            h.join([mk("m1")], now=NOW + 5)
            h.join([mk("m2"), mk("m3", created_at=NOW + 40)], now=NOW + 7)
        resets = [answers(got)[3] for _kind, got in h.results]
        assert resets == [[NOW + 60_000], [NOW + 60_005],
                          [NOW + 60_007, NOW + 60_040]]
    finally:
        eng.close()


# ---- who waits, who does not ------------------------------------------------


def test_a_lone_call_waits_for_nobody_and_is_a_flush_of_one():
    eng = device_engine()
    try:
        before = flush_calls(eng)
        cols = columns([mk("x"), mk("y")])
        for _ in range(5):
            assert eng.check_columns(cols, now=NOW) is not None
        assert flush_calls(eng) == (before[0] + 5, before[1] + 5)
        assert joins(eng) == 0
        assert eng._gate.active == 0 and not eng._gate.waiting
        assert all(r["calls"] == 1 and r["stages_us"]["join"] == 0
                   for r in eng.metrics.recorder.snapshot())
    finally:
        eng.close()


@pytest.mark.parametrize("k", [1, 2, 7])
def test_a_held_host_stage_makes_k_calls_one_flush(k):
    eng = device_engine()
    try:
        before = flush_calls(eng)
        batches = eng.metrics.batches
        with Held(eng) as h:
            h.first([mk("blocker")])
            for i in range(k):
                h.join([mk(f"k{i}"), mk("shared", limit=100)])
            assert eng._gate.items == 2 * k
        assert flush_calls(eng) == (before[0] + 2, before[1] + 1 + k)
        assert eng.metrics.batches == batches + 2
        shared = [answers(got)[2][1] for _kind, got in h.results[1:]]
        assert shared == [99 - i for i in range(k)]  # arrival order
    finally:
        eng.close()


def big_call():
    return [mk(f"big{i}") for i in range(17)]  # two of them overflow 32 lanes


def no_batching_call():
    return [mk("nb0"), mk("nb1", behavior=int(Behavior.NO_BATCHING))]


@pytest.mark.parametrize("reqs", [big_call, no_batching_call])
def test_what_may_not_join_goes_straight_through(reqs):
    eng = device_engine()
    try:
        assert eng._join_budget() == 32
        before = flush_calls(eng)
        with Held(eng) as h:
            h.first([mk("blocker")])
            h.submit(reqs())
            wait_for(lambda: eng._gate.active == 2, "the second flush")
            assert not eng._gate.waiting
        assert flush_calls(eng) == (before[0] + 2, before[1] + 2)
        assert joins(eng) == 0
        assert all(kind == "ok" and got is not None for kind, got in h.results)
    finally:
        eng.close()


def test_a_call_with_select_goes_straight_through():
    from gubernator_tpu import native

    eng = device_engine()
    try:
        cols = columns([mk("s0"), mk("s1"), mk("s2")])
        hashes = native.hash128_batch_raw(
            cols.key_data.tobytes(), cols.key_offsets, eng.cfg.num_groups
        )
        with Held(eng, serve=lambda _r, now: eng.check_columns(
            cols, now=now, select=np.array([0, 2]), hashes=hashes
        )) as h:
            h.submit(None)
            wait_for(lambda: eng._gate.active == 1, "the first call")
            h.submit(None)
            wait_for(lambda: eng._gate.active == 2, "the second call")
            assert not eng._gate.waiting
        # both stood at the engine lock: either may have had it first
        assert sorted(answers(got)[2] for _k, got in h.results) == [[8, 8], [9, 9]]
    finally:
        eng.close()


def test_a_full_batch_sends_the_next_call_straight_through():
    eng = device_engine()
    try:
        with Held(eng) as h:
            h.first([mk("blocker")])
            for i in range(2):
                h.join([mk(f"f{i}_{j}") for j in range(16)])
            assert eng._gate.items == 32
            h.submit([mk("late")])
            wait_for(lambda: eng._gate.active == 2, "the call that found it full")
            assert len(eng._gate.waiting) == 2
        assert all(kind == "ok" and got is not None for kind, got in h.results)
    finally:
        eng.close()


def test_the_budget_is_the_narrowest_warm_width_and_no_more_than_the_ladders():
    eng = device_engine(batch_size=512)
    try:
        assert eng._ladder() == [128, 256]
        eng._warm_shapes, eng._warm_stacks = (512,), ((8, 512),)
        assert eng._join_budget() == 128  # nothing narrower is warm (yet)
        eng._warm_shapes = (512, 128, 256)
        assert eng._join_budget() == 128
        assert eng._may_join(columns([mk(f"p{i}") for i in range(64)]), 0)
        assert not eng._may_join(columns(
            [mk(f"p{i}") for i in range(65)]), 0)  # no second one beside it
        assert not eng._may_join(columns([mk("p")] * 2), 127)  # batch is full
    finally:
        eng.close()


# ---- failure ----------------------------------------------------------------


def test_a_merged_batch_above_max_waves_is_one_flush_of_several_launches():
    """Three members hit one key twice each, and a fourth one of its own
    five times: six waves merged, over max_waves 4, so the one merged
    flush runs them as two launches and every member is answered as the
    same calls served one after another are. (Until ISSUE 44 the merged
    assembly was refused and each member served alone, the fourth by the
    object path.)"""
    merged, serial = device_engine(max_waves=4), device_engine(max_waves=4)
    calls = [[mk("hot", limit=5), mk("hot", limit=5)] for _ in range(3)]
    calls.append([mk("own", limit=9)] * 5)
    try:
        before = flush_calls(merged)
        with Held(merged) as h:
            h.first([mk("blocker")])
            for i, reqs in enumerate(calls):
                h.join(reqs, now=NOW + 1 + i)
        want = [
            serial.check_columns(columns(reqs), now=NOW + 1 + i)
            for i, reqs in enumerate(calls)
        ]
        for (kind, got), exp in zip(h.results[1:], want):
            assert kind == "ok" and answers(got) == answers(exp)
        assert [answers(g)[0] for _k, g in h.results[1:4]] == [
            [0, 0], [0, 0], [0, 1]]
        assert answers(h.results[4][1])[2] == [8, 7, 6, 5, 4]
        # the blocker's flush and the merged one, which served four calls
        assert flush_calls(merged) == (before[0] + 2, before[1] + 5)
        rec = merged.metrics.recorder.last()
        assert (rec["calls"], rec["waves"], rec["launches"]) == (4, 6, 2)
        # the merged flush; alone, only the fourth call is over max_waves
        assert over_max_waves(merged) == over_max_waves(serial) == 1
    finally:
        merged.close()
        serial.close()


def stub_service(eng):
    return SimpleNamespace(
        engine=eng, fast_edge=True, picker=None, region_mgr=None,
        global_mgr=None, force_global=False,
    )


@pytest.mark.parametrize("error,lands", [
    (TableCommittedError("waves committed"), "raised"),
    (RuntimeError("before the commit"), "object path"),
])
def test_an_error_in_the_merged_flush_reaches_every_member(error, lands):
    """Through the serving edge (fastpath.try_serve): a committed table
    must surface in every member's call, any other error sends every
    member to the object path (None), the leader included."""
    eng = device_engine()
    svc = stub_service(eng)
    real = eng._execute_waves
    launches = []

    def execute(*a, **kw):
        launches.append(1)
        if len(launches) == 2:  # the merged flush
            raise error
        return real(*a, **kw)

    eng._execute_waves = execute
    try:
        serve = lambda reqs, _now: fastpath.try_serve(  # noqa: E731
            svc, wire_bytes(reqs), False)
        with Held(eng, serve=serve) as h:
            h.first([mk("blocker")])
            for i in range(4):
                h.join([mk(f"e{i}"), mk("shared")])
        assert h.results[0][0] == "ok" and isinstance(h.results[0][1], bytes)
        for kind, got in h.results[1:]:
            if lands == "raised":
                assert kind == "raised" and isinstance(got, TableCommittedError)
            else:
                assert (kind, got) == ("ok", None)
        assert len(launches) == 2
        # the engine serves on: the gate holds nothing back
        eng._execute_waves = real
        assert eng.check_columns(columns([mk("after")]), now=NOW) is not None
    finally:
        eng.close()


# ---- exact consumption ------------------------------------------------------


@pytest.mark.parametrize("engine", ["device", "store"])
def test_exact_consumption_under_32_threads_on_ten_keys(engine):
    """Every acknowledged hit is counted exactly once and OVER_LIMIT
    never consumes, whatever merges: per key the accepted hits carry
    remaining limit-1 .. limit-n each once, and a probe reads the rest."""
    eng = ENGINES[engine]()
    limit, threads, rounds = 60, 32, 12
    seen = [[] for _ in range(threads)]
    start = threading.Barrier(threads)

    def caller(t):
        rng = np.random.default_rng(t)
        start.wait()
        for _ in range(rounds):
            a, b = rng.choice(10, size=2, replace=False).tolist()
            reqs = [mk(f"x{a}", limit=limit), mk(f"x{b}", limit=limit)]
            out = eng.check_columns(columns(reqs))
            assert out is not None
            seen[t].append(((a, b), answers(out)))

    interval = sys.getswitchinterval()
    try:
        before = flush_calls(eng)
        sys.setswitchinterval(1e-4)  # more hand-overs between the threads
        pool = [threading.Thread(target=caller, args=(t,)) for t in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=120)
            assert not t.is_alive()
        sys.setswitchinterval(interval)
        flushes, calls = (x - y for x, y in zip(flush_calls(eng), before))
        assert calls == threads * rounds and 1 <= flushes <= calls
        accepted = {k: [] for k in range(10)}
        attempts = dict.fromkeys(range(10), 0)
        for per_thread in seen:
            for keys, (status, lim, remaining, _reset) in per_thread:
                assert lim == [limit, limit]
                for k, st, rem in zip(keys, status, remaining):
                    attempts[k] += 1
                    if st == 0:
                        accepted[k].append(rem)
                    else:
                        assert rem == 0
        probe = eng.check_columns(columns(
            [mk(f"x{k}", hits=0, limit=limit) for k in range(10)]))
        for k in range(10):
            n = min(limit, attempts[k])
            assert sorted(accepted[k], reverse=True) == list(
                range(limit - 1, limit - 1 - n, -1)), k
            assert int(probe[2][k]) == limit - n
        assert sum(attempts.values()) == 2 * threads * rounds
        assert (eng._gate.active, eng._gate.waiting) == (0, [])
    finally:
        sys.setswitchinterval(interval)
        eng.close()


# ---- the capture's reader ---------------------------------------------------


def test_profile_gaps_names_the_leaders_wait():
    """A merged flush's leader waited 2..5 for its turn (the flush before
    it had not launched), then assembled and launched the program that
    starts at 8: the gap's part under `flush.join` is named `join`."""
    from tools import profile_gaps

    spans = [
        (1.0, 1.0, "rpc.begin", 7, 0),
        (1.5, 2.0, "call.parse", 7, 0),
        (2.0, 5.0, "flush.join", 7, 3),
        (5.0, 6.0, "flush.waves", 7, 3),
        (6.0, 7.5, "flush.dispatch", 7, 3),
    ]
    got = profile_gaps.attribute_plane([(8.0, 9.0)], spans, 0.0, 9.0)
    assert got == pytest.approx({
        profile_gaps.NOT_YET: 1.0, "executor_wait": 0.5, "parse": 0.5,
        "join": 3.0, "waves": 1.0, "dispatch": 1.5,
        profile_gaps.UNATTRIBUTED: 0.5,
    })
    assert "join" in profile_gaps.ORDER
