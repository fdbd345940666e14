"""Continuous-batching device pipeline (ISSUE 6): the dispatch stage
launches kernels without a host sync while the completion stage syncs
in-flight tickets in FIFO order. Pinned invariants: depth 1 reproduces
the serial pump bit-exactly, futures resolve in dispatch order, the
in-flight ring is bounded (backpressure), a failed ticket fails only its
own futures and rebuilds the table exactly once, and drain/close serves
dispatched-but-unsynced flushes (zero loss)."""

import threading
import time

import pytest

from gubernator_tpu.api.types import Behavior, RateLimitReq
from gubernator_tpu.ops.kernels import LAYOUTS
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig

NOW = 1_753_700_000_000


def mk(key="k", **kw):
    kw.setdefault("name", "pipe")
    kw.setdefault("duration", 60_000)
    kw.setdefault("limit", 100)
    kw.setdefault("hits", 1)
    return RateLimitReq(unique_key=key, **kw)


def _trace(n=400, n_keys=23):
    """Deterministic mixed trace: duplicate keys (multi-wave flushes),
    leaky + token buckets, over-limit pressure, RESET_REMAINING."""
    import random

    rng = random.Random(7)
    reqs = []
    for i in range(n):
        k = f"k{rng.randrange(n_keys)}"
        behavior = 0
        if i % 37 == 5:
            behavior = int(Behavior.RESET_REMAINING)
        reqs.append(
            mk(
                key=k,
                algorithm=rng.choice((0, 1)),
                hits=rng.choice((0, 1, 1, 2, 5)),
                limit=20,
                behavior=behavior,
            )
        )
    return reqs


def _run(depth, reqs, layout="fused", chunk=50):
    """Submit the trace as overlapping bulks (pipelining actually engages
    at depth >= 2) and return the flat decision tuples."""
    eng = DeviceEngine(
        EngineConfig(
            num_groups=1 << 10, batch_size=64, batch_wait_s=0.001,
            pipeline_depth=depth, layout=layout,
        ),
        now_fn=lambda: NOW,
    )
    try:
        futs = [
            eng.check_bulk(reqs[i : i + chunk])
            for i in range(0, len(reqs), chunk)
        ]
        out = [r for f in futs for r in f.result(timeout=30)]
    finally:
        eng.close()
    return [(r.status, r.limit, r.remaining, r.reset_time, r.error) for r in out]


def test_depth1_matches_depth2_bitexact():
    reqs = _trace()
    import dataclasses

    a = _run(1, [dataclasses.replace(r) for r in reqs])
    b = _run(2, [dataclasses.replace(r) for r in reqs])
    assert a == b


@pytest.mark.parametrize("depth", [3, 4])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_pipelined_matches_serial_all_layouts(layout, depth):
    """Bit-exact on both table layouts with pipelining on (the
    engine-level twin of the kernel fuzz suite's acceptance)."""
    import dataclasses

    reqs = _trace(n=120, n_keys=11)
    a = _run(1, [dataclasses.replace(r) for r in reqs], layout=layout)
    b = _run(depth, [dataclasses.replace(r) for r in reqs], layout=layout)
    assert a == b


def test_fifo_future_resolution_order():
    """At depth >= 2 futures still resolve in dispatch order — the
    completion stage is FIFO, never a racing pool."""
    eng = DeviceEngine(
        EngineConfig(
            num_groups=1 << 10, batch_size=32, batch_wait_s=0.0005,
            pipeline_depth=4,
        ),
        now_fn=lambda: NOW,
    )
    order = []
    lock = threading.Lock()
    try:
        futs = []
        for i in range(40):
            f = eng.check_async(
                mk(key=f"fifo{i}", behavior=Behavior.NO_BATCHING)
            )
            f.add_done_callback(
                lambda _f, i=i: (lock.acquire(), order.append(i),
                                 lock.release())
            )
            futs.append(f)
        for f in futs:
            assert f.result(timeout=10).error == ""
    finally:
        eng.close()
    assert order == sorted(order)


def test_backpressure_bounds_inflight_ring():
    """The pump blocks when the in-flight ring is full: with completion
    gated, at most `pipeline_depth` tickets are ever in flight."""
    eng = DeviceEngine(
        EngineConfig(
            num_groups=1 << 10, batch_size=32, batch_wait_s=0.0005,
            pipeline_depth=2,
        ),
        now_fn=lambda: NOW,
    )
    gate = threading.Event()
    orig = eng._complete
    max_seen = []

    def gated(t):
        max_seen.append(eng._inflight)
        gate.wait(10)
        orig(t)

    eng._complete = gated
    try:
        futs = [
            eng.check_async(mk(key=f"bp{i}", behavior=Behavior.NO_BATCHING))
            for i in range(8)
        ]
        deadline = time.monotonic() + 2
        while time.monotonic() < deadline:
            assert eng._inflight <= 2
            time.sleep(0.01)
        gate.set()
        for f in futs:
            assert f.result(timeout=10).error == ""
    finally:
        gate.set()
        eng.close()
    assert max_seen and max(max_seen) <= 2


class _FailingKernels:
    """Per-instance kernel proxy: runs the real decide (consuming the
    donated table) then raises on the armed call — the worst-case
    in-flight failure, a consumed table mid-ring."""

    def __init__(self, real):
        self._real = real
        self.fail_on_call = -1
        self.decide_calls = 0
        self.creates = 0

    def __getattr__(self, name):
        return getattr(self._real, name)

    def create(self, *a, **kw):
        self.creates += 1
        return self._real.create(*a, **kw)

    def decide_packed(self, *a, **kw):
        # the launch the engine makes: one uploaded operand in, one
        # output vector out
        self.decide_calls += 1
        out = self._real.decide_packed(*a, **kw)
        if self.decide_calls == self.fail_on_call:
            raise RuntimeError("injected device failure")
        return out


def test_failed_flush_fails_only_its_futures_and_rebuilds_once():
    eng = DeviceEngine(
        EngineConfig(
            num_groups=1 << 10, batch_size=32, batch_wait_s=0.0005,
            pipeline_depth=3,
        ),
        now_fn=lambda: NOW,
    )
    try:
        proxy = _FailingKernels(eng.K)
        eng.K = proxy
        ok1 = [
            eng.check_async(mk(key=f"a{i}", behavior=Behavior.NO_BATCHING))
            for i in range(3)
        ]
        for f in ok1:
            assert f.result(timeout=10).error == ""
        # Arm the NEXT decide call: that flush's donated table is
        # consumed by the real decide before the raise.
        proxy.fail_on_call = proxy.decide_calls + 1
        boom = eng.check_async(mk(key="boom", behavior=Behavior.NO_BATCHING))
        resp = boom.result(timeout=10)
        assert "injected device failure" in resp.error
        # Only the failed flush errored; the engine rebuilt ONCE and
        # keeps serving.
        ok2 = [
            eng.check_async(mk(key=f"b{i}", behavior=Behavior.NO_BATCHING))
            for i in range(3)
        ]
        for f in ok2:
            assert f.result(timeout=10).error == ""
        assert proxy.creates == 1, "table must rebuild exactly once"
    finally:
        eng.close()


def test_completion_stage_failure_is_ticket_isolated():
    """A failure while MATERIALIZING one in-flight ticket fails that
    ticket's futures only; earlier and later tickets resolve."""
    eng = DeviceEngine(
        EngineConfig(
            num_groups=1 << 10, batch_size=32, batch_wait_s=0.0005,
            pipeline_depth=3,
        ),
        now_fn=lambda: NOW,
    )
    orig = eng._complete

    def flaky(t):
        if any(req.unique_key == "poison" for req, _ in t.items):
            raise RuntimeError("injected completion failure")
        orig(t)

    eng._complete = flaky
    try:
        # Sequential waits pin one ticket per request (a shared flush
        # would legitimately fail all of its members).
        a = eng.check_async(mk(key="pre", behavior=Behavior.NO_BATCHING))
        assert a.result(timeout=10).error == ""
        p = eng.check_async(mk(key="poison", behavior=Behavior.NO_BATCHING))
        assert "injected completion failure" in p.result(timeout=10).error
        b = eng.check_async(mk(key="post", behavior=Behavior.NO_BATCHING))
        assert b.result(timeout=10).error == ""
    finally:
        eng._complete = orig
        eng.close()


def test_pipeline_telemetry_populated():
    """The in-flight-depth and overlap-ratio histograms sample every
    flush (serial mode pins depth=1 / overlap=0)."""
    eng = DeviceEngine(
        EngineConfig(
            num_groups=1 << 10, batch_size=64, batch_wait_s=0.0005,
            pipeline_depth=2,
        ),
        now_fn=lambda: NOW,
    )
    try:
        futs = [
            eng.check_bulk([mk(key=f"t{j}{i}") for j in range(20)])
            for i in range(10)
        ]
        for f in futs:
            f.result(timeout=10)
        em = eng.metrics
        assert em.pipeline_inflight.summary()["count"] >= 1
        assert em.pipeline_overlap.summary()["count"] >= 1
        snap = eng.debug_snapshot()
        assert snap["pipeline_depth"] == 2
    finally:
        eng.close()


def test_ici_depth1_matches_depth2():
    """Both ici tiers (sharded + replica) through the pipeline: depth 1
    and depth 2 produce identical decisions for a mixed GLOBAL /
    non-GLOBAL trace."""
    import dataclasses

    from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig

    def run(depth):
        eng = IciEngine(
            IciEngineConfig(
                num_groups=1 << 10, num_slots=1 << 12, batch_size=64,
                batch_wait_s=0.001, pipeline_depth=depth,
                # No background sync ticks mid-trace: a tick merges the
                # replica tier and would make results timing-dependent.
                sync_wait_s=30.0,
            ),
            now_fn=lambda: NOW,
        )
        try:
            reqs = []
            for i in range(120):
                behavior = int(Behavior.GLOBAL) if i % 3 == 0 else 0
                reqs.append(
                    mk(key=f"i{i % 17}", behavior=behavior, limit=50)
                )
            futs = [
                eng.check_bulk(
                    [dataclasses.replace(r) for r in reqs[i : i + 40]]
                )
                for i in range(0, len(reqs), 40)
            ]
            out = [r for f in futs for r in f.result(timeout=30)]
        finally:
            eng.close()
        return [(r.status, r.limit, r.remaining, r.error) for r in out]

    assert run(1) == run(2)
