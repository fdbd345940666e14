"""Device-tier telemetry: engine histograms populate from the serving
paths, the flight recorder captures flush records, the cold-compile
counter pins the "serving path never compiles" invariant (both the
warmed-engine 0 and the deliberately-cold detection), and the occupancy
gauges reflect table state."""

import os

import pytest

from gubernator_tpu.api.types import Behavior, RateLimitReq
from gubernator_tpu.ops.layout import WaveOperand
from gubernator_tpu.runtime import telemetry
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig
from gubernator_tpu.runtime.telemetry import FlightRecorder

NOW = 1_753_700_000_000


@pytest.fixture
def engine():
    clock = {"now": NOW}
    eng = DeviceEngine(
        EngineConfig(num_groups=1 << 10, batch_size=64, batch_wait_s=0.002),
        now_fn=lambda: clock["now"],
    )
    eng._test_clock = clock
    yield eng
    eng.close()


def mk(key="k", **kw):
    kw.setdefault("name", "t")
    kw.setdefault("duration", 60_000)
    kw.setdefault("limit", 10)
    kw.setdefault("hits", 1)
    return RateLimitReq(unique_key=key, **kw)


# ---- flight recorder primitive ---------------------------------------------


def test_flight_recorder_ring_and_seq():
    fr = FlightRecorder(capacity=4)
    for i in range(7):
        fr.record(n=i)
    snap = fr.snapshot()
    assert len(snap) == 4  # ring capacity
    assert [r["n"] for r in snap] == [3, 4, 5, 6]  # newest last
    assert [r["seq"] for r in snap] == [4, 5, 6, 7]  # monotonic ids
    assert fr.last()["n"] == 6
    assert all("ts" in r for r in snap)


# ---- engine-side wiring -----------------------------------------------------


def test_flush_populates_histograms_and_recorder(engine):
    engine.check_batch([mk("a"), mk("a"), mk("b"), mk("c")])
    em = engine.metrics
    assert em.flush_duration.summary()["count"] >= 1
    assert em.device_sync.summary()["count"] >= 1
    assert em.queue_wait.summary()["count"] >= 1
    assert em.flush_waves.summary()["count"] >= 1
    # 2x "a" in one flush -> at least one 2-wave flush observed (the
    # quantile interpolates within the (1, 2] bucket, so > 1 proves a
    # multi-wave sample landed)
    assert em.flush_waves.summary()["p99"] > 1
    recs = em.recorder.snapshot()
    assert recs, "flush must leave a flight record"
    r = recs[-1]
    assert r["path"] == "object"
    assert r["layout"] == engine.cfg.layout
    assert r["waves"] >= 2 and r["n"] == 4 and r["carry"] == 0
    assert len(r["widths"]) == r["waves"]
    assert r["dur_us"] >= r["dev_us"] >= 0


def test_debug_snapshot_shape(engine):
    engine.check_batch([mk("x")])
    snap = engine.debug_snapshot()
    assert snap["engine"] == "DeviceEngine"
    assert snap["layout"] == engine.cfg.layout
    assert snap["counters"]["requests"] == 1
    assert snap["counters"]["cold_compiles"] == 0
    assert "gubernator_engine_flush_duration" in snap["histograms"]
    assert snap["occupancy"]["live"] == 1
    assert snap["flight_recorder"]


def test_occupancy_stats(engine):
    engine.check_batch([mk(f"k{i}") for i in range(32)])
    stats = engine.occupancy_stats()
    assert stats["live"] == 32
    assert stats["slots"] == (1 << 10) * 8
    assert stats["occupancy"] == pytest.approx(32 / stats["slots"])
    assert stats["full_group_ratio"] == 0.0  # nowhere near full


def test_full_group_ratio_detects_pressure():
    eng = DeviceEngine(
        EngineConfig(num_groups=4, ways=2, batch_size=16,
                     batch_wait_s=0.001),
        now_fn=lambda: NOW,
    )
    try:
        # 8 slots total; 32 distinct keys overfill every group
        eng.check_batch([mk(f"p{i}", limit=100) for i in range(32)])
        stats = eng.occupancy_stats()
        assert stats["full_group_ratio"] == 1.0
        assert stats["occupancy"] == 1.0
    finally:
        eng.close()


# ---- cold-compile invariant -------------------------------------------------


def test_warmed_engine_serving_never_compiles(engine):
    """The regression pin for engine warmup: batch path, duplicate-key
    waves, and NO_BATCHING single flushes must all dispatch only warm
    shapes — zero cold compiles."""
    engine.check_batch([mk(f"w{i}") for i in range(50)])
    engine.check_batch([mk("dup"), mk("dup"), mk("dup")])
    engine.check_batch([mk("nb", behavior=Behavior.NO_BATCHING)])
    assert engine.metrics.cold_compiles == 0


def test_deliberate_cold_dispatch_is_detected(engine):
    """A serving-scope dispatch at a never-warmed shape must increment
    the counter — proves the detection machinery actually fires (the
    0 above is not a dead sensor)."""
    scratch = engine.K.create(32, 4)  # geometry the engine never warmed
    with telemetry.serving_scope(engine.metrics):
        engine.K.decide_packed(scratch, WaveOperand.zeros(8).stamp(NOW).buf, 4)
    assert engine.metrics.cold_compiles > 0
    # and the same dispatch OUTSIDE a serving scope is not counted
    before = engine.metrics.cold_compiles
    scratch2 = engine.K.create(16, 4)
    engine.K.decide_packed(scratch2, WaveOperand.zeros(4).stamp(NOW).buf, 4)
    assert engine.metrics.cold_compiles == before


def test_completion_thread_compile_is_counted(monkeypatch):
    """The pipelined engine materializes outputs on the completion
    thread, outside the pump's dispatch-site serving scope — a compile
    fired there must still be attributed to the engine (the
    _complete_ticket serving_scope regression pin)."""
    from gubernator_tpu.runtime import engine as engine_mod

    eng = DeviceEngine(
        EngineConfig(num_groups=1 << 8, ways=4, batch_size=32,
                     batch_wait_s=0.001, pipeline_depth=2),
        now_fn=lambda: NOW,
    )
    try:
        assert eng.metrics.cold_compiles == 0
        real = engine_mod._read_waves
        fired = {"n": 0}
        # geometry this process never compiled (48 groups, width 12)
        scratch = eng.K.create(48, 4)

        def cold_then_real(*a, **kw):
            if fired["n"] == 0:
                fired["n"] = 1
                eng.K.decide_packed(
                    scratch, WaveOperand.zeros(12).stamp(NOW).buf, 4
                )
            return real(*a, **kw)

        monkeypatch.setattr(engine_mod, "_read_waves", cold_then_real)
        eng.check_batch([mk(f"c{i}") for i in range(10)])
        assert fired["n"] == 1
        assert eng.metrics.cold_compiles > 0
    finally:
        eng.close()


# ---- ICI tier ---------------------------------------------------------------


# 512 replica groups: the default cap is no cap (a full tick, as wide
# as the table); a cap of 64 merges at 1, 8 or 64 groups.
@pytest.mark.parametrize("cap,widths", [(65536, (512,)), (64, (1, 8, 64))])
def test_ici_tick_telemetry(cap, widths):
    from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig

    eng = IciEngine(
        IciEngineConfig(
            num_groups=1 << 9, num_slots=1 << 11, batch_size=64,
            batch_wait_s=0.002, sync_wait_s=3600,  # manual ticks only
            max_sync_groups=cap,
        ),
        now_fn=lambda: NOW,
    )
    try:
        eng.check_batch(
            [mk(f"g{i}", behavior=Behavior.GLOBAL) for i in range(10)]
            + [mk(f"s{i}") for i in range(10)]
        )
        eng.sync_now()
        em = eng.metrics
        assert em.ici_tick_duration.summary()["count"] == 1
        assert em.ici_tick_groups.summary()["count"] == 1
        assert em.flush_duration.summary()["count"] >= 1
        tick = [
            r for r in em.recorder.snapshot() if r["path"] == "ici-sync"
        ]
        assert len(tick) == 1
        assert tick[0]["groups"] >= 1  # GLOBAL traffic dirtied groups
        assert tick[0]["backlog"] == 0
        # merged at the least width of the ladder that held them
        assert em.ici_tick_width.summary()["count"] == 1
        assert tick[0]["width"] == min(
            w for w in widths if w >= tick[0]["groups"]
        )
        # ... and the benchmark's reader of the two histograms finds
        # them under the names it asks /metrics for.
        from benchmarks import readers
        from gubernator_tpu.metrics import Metrics, wire_engine_telemetry

        m = Metrics()
        wire_engine_telemetry(m, eng)
        series = {}
        for line in m.render().decode().splitlines():
            name, _, value = line.rpartition(" ")
            if name and not line.startswith("#"):
                series[name] = float(value)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        fill = readers.read(
            os.path.join(root, "benchmarks", "metrics", "ici_tick_fill.json"),
            readers.Context(
                before={}, after=series, device={}, phases={}, generator={},
                trace=None, conf={}, traffic={}, table={}, items_answered=0,
                root=root,
            ),
        )
        assert fill == pytest.approx(
            100.0 * tick[0]["groups"] / tick[0]["width"]
        )
        # warmed tick + warmed serving path: still zero cold compiles
        assert em.cold_compiles == 0
        snap = eng.debug_snapshot()
        assert snap["engine"] == "IciEngine"
        assert snap["occupancy"]["live"] >= 20
    finally:
        eng.close()


def test_serving_scope_nests_and_restores():
    class Owner:
        def __init__(self):
            self.n = 0

        def note_cold_compile(self):
            self.n += 1

    a, b = Owner(), Owner()
    with telemetry.serving_scope(a):
        with telemetry.serving_scope(b):
            telemetry._on_event_duration(telemetry._COMPILE_EVENT, 0.1)
        telemetry._on_event_duration(telemetry._COMPILE_EVENT, 0.1)
    telemetry._on_event_duration(telemetry._COMPILE_EVENT, 0.1)  # unscoped
    telemetry._on_event_duration("/jax/other_event", 0.1)  # wrong event
    assert (a.n, b.n) == (1, 1)
