"""The Store is handed flushes in the order they held the engine lock
(ISSUE 42). A wave's output vector and its packed rows are read after
the engine lock is released, so between a flush's release and its
write-behind another flush may already hold the lock: the hand-over lock
(runtime/engine.py `_StoreWaves`) keeps the Store's order, and a
Store.get under the engine lock sees every earlier flush's write-behind.

Every case is an order or a count, never a time: the first flush's
deferred read is held back by a patched reader until the second flush is
seen waiting (gubernator_store_handover_waits).
"""

import threading
import time

import pytest

from gubernator_tpu import wire
from gubernator_tpu.api.types import RateLimitReq
from gubernator_tpu.runtime import engine as engine_mod
from gubernator_tpu.runtime.engine import (
    DeviceEngine,
    EngineConfig,
    TableCommittedError,
)
from gubernator_tpu.service import pb
from gubernator_tpu.store import MemoryStore, attach_store

NOW = 1_753_700_000_000

pytestmark = pytest.mark.skipif(
    not wire.available(), reason="native wirepath unavailable"
)


class OrderStore(MemoryStore):
    """MemoryStore that keeps what it was asked and handed, in order."""

    def __init__(self):
        super().__init__()
        self.log = []

    def on_change(self, items):
        self.log += [("change", s.key, s.remaining) for s in items]
        super().on_change(items)

    def get(self, req):
        snap = super().get(req)
        self.log.append(
            ("get", req.hash_key(), None if snap is None else snap.remaining)
        )
        return snap


def engine():
    eng = DeviceEngine(
        EngineConfig(num_groups=64, ways=8, batch_size=64,
                     batch_wait_s=0.001),
        now_fn=lambda: NOW,
    )
    store = OrderStore()
    attach_store(eng, store)
    return eng, store


def req(key, hits=1):
    return RateLimitReq(name="ho", unique_key=key, limit=20,
                        duration=3_600_000, hits=hits)


def columns(reqs):
    msg = pb.pb.GetRateLimitsReq()
    for r in reqs:
        msg.requests.append(pb.req_to_pb(r))
    return wire.parse_requests(msg.SerializeToString())


def handover_waits(eng) -> float:
    for ln in eng.metrics.store_handover_waits.render_lines():
        if not ln.startswith("#"):
            return float(ln.rpartition(" ")[2])
    raise KeyError("gubernator_store_handover_waits")


def wait_for(what, seconds=30.0):
    deadline = time.monotonic() + seconds
    while not what():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.002)


class HeldReader:
    """engine._read_waves, its first call (the first flush's deferred
    read) held back until let_go()."""

    def __init__(self, monkeypatch):
        self.real = engine_mod._read_waves
        self.entered = threading.Event()
        self.go = threading.Event()
        self.calls = 0
        monkeypatch.setattr(engine_mod, "_read_waves", self)

    def __call__(self, *a, **kw):
        self.calls += 1
        if self.calls == 1:
            self.entered.set()
            assert self.go.wait(30.0), "the held read was never let go"
        return self.real(*a, **kw)

    def let_go(self):
        self.go.set()


@pytest.mark.parametrize("second", [
    "get_under_the_lock", "end_of_its_hold", "stacked_end_of_its_hold",
])
def test_the_store_sees_the_flushes_in_the_order_of_the_engine_lock(
        monkeypatch, second):
    """Two flushes from two threads, the first one's deferred read held
    back. `end_of_its_hold`: the second flush (another key) launches,
    and waits for its turn at the Store as the last thing under the
    lock: the Store is handed the first flush's change before the
    second's. `get_under_the_lock`: the second flush asks for the first
    one's key, which the table has lost meanwhile, so it reads through
    with Store.get under the lock: it waits first, and the answer
    continues from the first flush's change, not from the Store's row
    of before it. `stacked_end_of_its_hold`: both flushes are stacked
    runs of resident keys (ISSUE 45), the second another key's: the
    same wait and the same order."""
    eng, store = engine()
    try:
        first = eng.check_columns(columns([req("a", hits=3)]), now=NOW)
        assert first[2].tolist() == [17]
        if second == "stacked_end_of_its_hold":
            seen = eng.check_columns(columns([req("b")]), now=NOW)
            assert seen[2].tolist() == [19]
        assert handover_waits(eng) == 0.0
        held = HeldReader(monkeypatch)
        got = {}

        def flush(name, reqs):
            got[name] = eng.check_columns(columns(reqs), now=NOW + 1)

        stacked = second == "stacked_end_of_its_hold"
        reqs1 = [req("a", hits=0), req("a")] if stacked else [req("a")]
        t1 = threading.Thread(target=flush, args=("first", reqs1))
        t1.start()
        assert held.entered.wait(30.0)  # launched, released, not read
        assert not eng._lock.locked() and eng._handover.locked()
        if second == "get_under_the_lock":
            # forget the rows behind the engine's back, keep the strings
            with eng._lock:
                eng.table = eng.K.create(eng.cfg.num_groups, eng.cfg.ways)
            reqs2 = [req("a")]
        elif stacked:
            reqs2 = [req("b", hits=0), req("b")]  # resident: a stacked run
        else:
            reqs2 = [req("b")]  # never seen: prefetched before the lock
        n_log = len(store.log)
        t2 = threading.Thread(target=flush, args=("second", reqs2))
        t2.start()
        wait_for(lambda: handover_waits(eng) == 1.0)
        assert eng._lock.locked()  # the wait is under the engine lock
        # nothing of the second flush has reached the Store, nor has it
        # been asked under the lock, while the first holds its turn
        assert [e for e in store.log[n_log:] if e[0] != "get"
                or e[1] == "ho_a"] == []
        held.let_go()
        t1.join(30.0)
        t2.join(30.0)
        assert not t1.is_alive() and not t2.is_alive()
        assert got["first"][2].tolist()[-1] == 16
        tail = [e for e in store.log[n_log:] if e[0] == "change"
                or e[1] == "ho_a"]
        if second == "get_under_the_lock":
            assert tail == [("change", "ho_a", 16), ("get", "ho_a", 16),
                            ("change", "ho_a", 15)]
            assert got["second"][2].tolist() == [15]
        elif stacked:
            assert tail == [("change", "ho_a", 16), ("change", "ho_b", 18)]
            assert got["second"][2].tolist() == [19, 18]
            rec = eng.metrics.recorder.last()
            assert (rec["sequence"], rec["waves"]) == ("stacked", 2)
        else:
            assert tail == [("change", "ho_a", 16), ("change", "ho_b", 19)]
            assert got["second"][2].tolist() == [19]
        assert handover_waits(eng) == 1.0
        assert not eng._handover.locked()
        assert eng.metrics.cold_compiles == 0
    finally:
        eng.close()


@pytest.mark.parametrize("path", ["columnar", "object"])
def test_a_read_that_fails_after_the_release_is_a_committed_flush(
        monkeypatch, path):
    """The deferred read raises: the table has committed, so the flush
    surfaces TableCommittedError (the serving edge then does not retry
    a columnar call through the object path; the pump answers its items
    with the error), it is launched once, and its turn at the Store is
    given up: the next flush is served, from the table's committed
    row."""
    eng, store = engine()
    try:
        assert eng.check_batch([req("a", hits=3)])[0].remaining == 17
        real = engine_mod._read_waves
        launches = []
        real_execute = eng._execute_waves

        def execute(*a, **kw):
            launches.append(1)
            return real_execute(*a, **kw)

        def broken(*a, **kw):
            raise RuntimeError("the read failed")

        eng._execute_waves = execute
        monkeypatch.setattr(engine_mod, "_read_waves", broken)
        if path == "columnar":
            with pytest.raises(TableCommittedError):
                eng.check_columns(columns([req("a")]), now=NOW + 1)
        else:  # the pump answers a failed flush's items with the error
            assert "the read failed" in eng.check_batch([req("a")])[0].error
        assert len(launches) == 1
        assert not eng._handover.locked() and not eng._lock.locked()
        monkeypatch.setattr(engine_mod, "_read_waves", real)
        # the failed flush's hit is in the table (16), not in the Store
        assert store.data["ho_a"].remaining == 17
        if path == "columnar":
            after = eng.check_columns(columns([req("a")]), now=NOW + 2)
            assert after[2].tolist() == [15]
        else:
            assert eng.check_batch([req("a")])[0].remaining == 15
        assert store.data["ho_a"].remaining == 15
        assert handover_waits(eng) == 0.0
    finally:
        eng.close()


def store_flushes(eng, sequence: str) -> float:
    return eng.metrics.store_flushes.labels(sequence).get()


@pytest.mark.parametrize("waves", [1, 3], ids=["a-wave", "stacked-run"])
def test_many_threads_hand_one_key_over_in_the_order_of_their_flushes(waves):
    """More threads than cores, each a flush of its own on one key, the
    interpreter switching often: whatever order the flushes took the
    engine lock in, the Store is handed the key's changes in that order,
    so what it holds of the key only ever goes down and ends at the
    table's own row. With the key three times a call every flush after
    the first is a stacked run (ISSUE 45): the hand-over is the same."""
    import os
    import sys

    eng, store = engine()
    threads, calls = 2 * (os.cpu_count() or 4), 12
    limit = threads * calls * waves + 5
    one = RateLimitReq(name="ho", unique_key="hot", limit=limit,
                       duration=3_600_000, hits=1)
    cols = columns([one] * waves)
    errors = []

    def caller():
        try:
            for _ in range(calls):
                assert eng.check_columns(cols, now=NOW) is not None
        except BaseException as e:  # surfaced below, in the test's thread
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=caller) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(120.0)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(interval)
        eng.close()
    assert errors == []
    # (calls that meet at the engine's gate share a flush, whose last
    # change of the key is the one handed over: steps of one or more)
    handed = [e[2] for e in store.log if e[0] == "change"]
    assert all(a > b for a, b in zip(handed, handed[1:])), handed
    assert handed[-1] == limit - threads * calls * waves
    assert not eng._handover.locked()
    stacked, per_wave = (store_flushes(eng, q) for q in ("stacked", "per_wave"))
    assert stacked + per_wave == len(handed)
    if waves > 1:  # all but the flushes that met the key before it was seen
        assert stacked > per_wave >= 1
    # (one item a call: the calls that met at the gate and shared a
    # flush made a run of the key's waves, stacked too)
    assert eng.metrics.store_stacked_surprises.labels().get() == 0
