"""The sync tick in three parts (ISSUE 38): `sync_now` observes
`lock_wait`, `launch` and `read` once a tick into
gubernator_ici_tick_stage_duration, they add up to no more than the
tick's own duration, a tick that has to wait for the engine lock shows
the wait under `lock_wait`, and in a capture each part is a span on the
tick thread's line. Counts and orderings, from a CPU run."""

import threading
import time

import pytest

from gubernator_tpu.api.types import Behavior, RateLimitReq
from gubernator_tpu.utils import tracing

NOW = 1_753_700_000_000
STAGES = ("lock_wait", "launch", "read")


def mk(key, **kw):
    return RateLimitReq(name="t", unique_key=key, duration=60_000, limit=10,
                        hits=1, **kw)


@pytest.fixture(scope="module")
def engine():
    from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig

    eng = IciEngine(
        IciEngineConfig(
            num_groups=1 << 9, num_slots=1 << 11, batch_size=64,
            batch_wait_s=0.002, sync_wait_s=3600,  # manual ticks only
        ),
        now_fn=lambda: NOW,
    )
    yield eng
    eng.close()


def stage_sums(eng) -> dict:
    """{stage: (count, sum)} of the tick's stage histogram."""
    out = {}
    for key, s in eng.metrics.ici_tick_stage_duration.label_summaries(
            qs=()).items():
        out[key[0]] = (s["count"], s["sum"])
    return out


def test_every_stage_is_exposed_at_zero_before_the_first_tick(engine):
    got = stage_sums(engine)
    assert set(got) == set(STAGES)
    assert all(v == (0, 0.0) for v in got.values())


@pytest.mark.parametrize("ticks", [1, 3])
def test_each_stage_is_observed_once_a_tick(engine, ticks):
    em = engine.metrics
    before = stage_sums(engine)
    t_before = em.ici_tick_duration.summary()
    engine.check_batch([mk(f"g{i}", behavior=Behavior.GLOBAL)
                        for i in range(5)])
    for _ in range(ticks):
        engine.sync_now()
    after = stage_sums(engine)
    t_after = em.ici_tick_duration.summary()
    assert t_after["count"] - t_before["count"] == ticks
    parts = 0.0
    for stage in STAGES:
        assert after[stage][0] - before[stage][0] == ticks, stage
        d = after[stage][1] - before[stage][1]
        assert d >= 0.0
        parts += d
    # the parts lie inside the tick: its first and last lines are the rest
    assert 0.0 < parts <= t_after["sum"] - t_before["sum"]


def test_a_tick_that_waits_for_the_engine_lock_says_so(engine):
    before = stage_sums(engine)
    held = threading.Event()
    release = threading.Event()

    def hold():
        with engine._lock:
            held.set()
            release.wait(5)

    t = threading.Thread(target=hold)
    t.start()
    assert held.wait(5)
    tick = threading.Thread(target=engine.sync_now)
    tick.start()
    time.sleep(0.05)
    release.set()
    t.join(5)
    tick.join(30)
    after = stage_sums(engine)
    assert after["lock_wait"][1] - before["lock_wait"][1] >= 0.04
    assert after["lock_wait"][0] - before["lock_wait"][0] == 1


def test_in_a_capture_the_parts_are_spans_in_their_order(engine, monkeypatch):
    log = []

    class Fake:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("open", self.name, threading.get_ident()))

        def __exit__(self, *exc):
            log.append(("close", self.name, threading.get_ident()))

    monkeypatch.setattr(tracing, "_annotation", lambda n, a: Fake(n))
    engine.sync_now()
    assert log == []  # no capture: nothing is built
    monkeypatch.setattr(tracing, "_capturing", True)
    engine.sync_now()
    mine = [(what, name) for what, name, tid in log
            if name.startswith("tick.") and tid == threading.get_ident()]
    assert mine == [
        ("open", "tick.lock_wait"), ("close", "tick.lock_wait"),
        ("open", "tick.launch"), ("close", "tick.launch"),
        ("open", "tick.read"), ("close", "tick.read"),
    ]
