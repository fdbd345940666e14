"""A launch crosses the host-device boundary once each way: one uploaded
operand in, one output array read (gubernator_engine_wave_transfers),
on every flush path. A run of equally wide waves of one flush is one
launch (ISSUE 35); with a Store every wave is a launch of its own. And
no launch under the engine lock passes the device anything from the host
(jax.transfer_guard around _execute_waves)."""

import jax
import pytest

from gubernator_tpu import wire
from gubernator_tpu.api.types import Behavior, RateLimitReq
from gubernator_tpu.metrics import Metrics, wire_engine_telemetry
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig, MeshEngine
from gubernator_tpu.service import pb
from gubernator_tpu.store import MemoryStore, attach_store

NOW = 1_753_700_000_000

needs_wire = pytest.mark.skipif(
    not wire.available(), reason="native wirepath unavailable"
)


def mk(key, **kw):
    kw.setdefault("duration", 60_000)
    kw.setdefault("limit", 1000)
    kw.setdefault("hits", 1)
    return RateLimitReq(name="wt", unique_key=key, **kw)


def columns(reqs):
    msg = pb.pb.GetRateLimitsReq()
    for r in reqs:
        msg.requests.append(pb.req_to_pb(r))
    return wire.parse_requests(msg.SerializeToString())


@pytest.fixture
def engine():
    eng = DeviceEngine(
        EngineConfig(num_groups=1 << 8, ways=4, batch_size=32,
                     batch_wait_s=0.001, max_waves=32),
        now_fn=lambda: NOW,
    )
    yield eng
    eng.close()


def transfers(eng):
    em = eng.metrics
    return em.wave_h2d, em.wave_d2h, em.waves


def flush_columnar(eng):
    """Three keys, one of them four times: a four-wave columnar flush."""
    out = eng.check_columns(
        columns([mk("a"), mk("b"), mk("dup"), mk("dup"), mk("dup"), mk("dup")]),
        now=NOW,
    )
    assert out is not None and out[2].tolist()[-1] == 996
    return 4, 1


def flush_pump(eng):
    """The object path through the pump: two waves."""
    got = eng.check_batch([mk("p1"), mk("p2"), mk("p2")])
    assert [r.remaining for r in got] == [999, 999, 998]
    return 2, 1


def flush_32_waves(eng):
    """One key 40 times: a full 32-wave flush, the carry in a second."""
    got = eng.check_batch([mk("hot") for _ in range(40)])
    assert [r.remaining for r in got] == list(range(999, 959, -1))
    assert any(r["waves"] == 32 for r in eng.metrics.recorder.snapshot())
    return 40, 2


FLUSHES = {
    "columnar": pytest.param(flush_columnar, marks=needs_wire),
    "pump": flush_pump,
    "waves32": flush_32_waves,
}


@pytest.mark.parametrize("flush", FLUSHES.values(), ids=FLUSHES.keys())
def test_one_operand_in_one_read_out_a_wave(engine, flush):
    """Each flush function returns (its waves, its launches): the waves
    of a flush are one width here, so a flush is one launch."""
    h0, d0, w0 = transfers(engine)
    waves, launches = flush(engine)
    h1, d1, w1 = transfers(engine)
    assert w1 - w0 == waves
    assert (h1 - h0, d1 - d0) == (launches, launches)


# With a Store a flush whose keys this process has never seen knows it
# reads through and runs wave by wave: an operand in and a read out a
# wave. The 40 of one key are a flush of 32 waves (never seen: 32) and
# the carry's 8, whose key is resident by then: one stacked run, 1.
STORE_TRANSFERS = {flush_columnar: 4, flush_pump: 2, flush_32_waves: 32 + 1}


@pytest.mark.parametrize("flush", FLUSHES.values(), ids=FLUSHES.keys())
def test_store_flush_counts_one_each_for_its_decide(engine, flush):
    """With a Store the count stays one each way a launch: a wave's
    where the flush runs the per-wave sequence, a run's where it runs
    stacked (tests/test_store_stacked.py has the stacked counts)."""
    store = MemoryStore()
    attach_store(engine, store)
    h0, d0, w0 = transfers(engine)
    waves, _launches = flush(engine)
    h1, d1, w1 = transfers(engine)
    assert w1 - w0 == waves
    assert (h1 - h0, d1 - d0) == (STORE_TRANSFERS[flush],) * 2
    assert store.data  # write-behind read the packed store columns


@needs_wire
def test_columnar_call_over_max_waves_counts_its_launches(engine):
    """33 of one key is over max_waves: two launches of one flush (32
    waves and 1), one operand in and one read out each. Until ISSUE 44
    the attempt was refused and counted nothing."""
    h0, d0, w0 = transfers(engine)
    out = engine.check_columns(
        columns([mk("over") for _ in range(33)]), now=NOW
    )
    assert out[2].tolist() == list(range(999, 966, -1))
    h1, d1, w1 = transfers(engine)
    assert (w1 - w0, h1 - h0, d1 - d0) == (33, 2, 2)
    rec = engine.metrics.recorder.last()
    assert (rec["waves"], rec["launches"]) == (33, 2)


def test_counter_is_exported_per_direction(engine):
    m = Metrics()
    wire_engine_telemetry(m, engine)
    launches = flush_pump(engine)[1] + flush_32_waves(engine)[1]
    text = m.render().decode()
    h2d = engine.metrics.wave_h2d
    assert h2d == engine.metrics.wave_d2h >= launches
    for direction in ("h2d", "d2h"):
        line = f'gubernator_engine_wave_transfers{{direction="{direction}"}}'
        got = [ln for ln in text.splitlines() if ln.startswith(line)]
        assert got and float(got[0].split()[-1]) == float(h2d), got
    ledger = engine.metrics.transfer_snapshot()
    assert ledger["h2d/serve"]["count"] >= 2  # one record a flush
    assert ledger["h2d/serve"]["bytes"] > 0


# ---- nothing from the host under the lock --------------------------------


@pytest.fixture
def guarded(monkeypatch):
    """Run every _execute_waves under a transfer guard that refuses an
    implicit host-to-device transfer: a launch that passed a numpy
    array or a Python scalar would raise. The explicit upload
    (utils/transfer.device_put in _upload) happens before it."""
    real = MeshEngine._execute_waves
    calls = []

    def under_guard(self, *a, **kw):
        calls.append(1)
        with jax.transfer_guard_host_to_device("disallow"):
            return real(self, *a, **kw)

    monkeypatch.setattr(MeshEngine, "_execute_waves", under_guard)
    return calls


@pytest.mark.parametrize("flush", FLUSHES.values(), ids=FLUSHES.keys())
def test_launches_pass_no_host_array(engine, guarded, flush):
    flush(engine)
    assert guarded
    assert engine.metrics.cold_compiles == 0


@needs_wire
def test_guard_catches_a_host_operand(engine, guarded, monkeypatch):
    """The guard is a live sensor: a launch handed the host buffer
    itself is refused."""
    monkeypatch.setattr(
        engine, "_upload",
        lambda waves, now, fs, stack=True: [
            (i, 1, w.stamp(now).buf) for i, w in enumerate(waves)
        ],
    )
    with pytest.raises(Exception, match="Disallowed host-to-device"):
        engine.check_columns(columns([mk("x")]), now=NOW)


def test_mesh_and_replica_launches_pass_no_host_array(guarded):
    """The four-chip forms on faked devices: the owner-sharded program
    and the replica tier's (GLOBAL) both launch from one replicated
    operand, on the object path and on the columnar split."""
    from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig

    eng = IciEngine(
        IciEngineConfig(
            num_groups=256, ways=4, num_slots=512, replica_ways=4,
            batch_size=32, sync_wait_s=3600.0,
        ),
        now_fn=lambda: NOW,
    )
    try:
        reqs = [mk("s1"), mk("s1"), mk("g1", behavior=int(Behavior.GLOBAL)),
                mk("g1", behavior=int(Behavior.GLOBAL)), mk("s2")]
        h0, d0, w0 = transfers(eng)
        got = eng.check_batch(reqs)
        # (each GLOBAL item lands on the next home device's replica)
        assert [r.remaining for r in got] == [999, 998, 999, 999, 999]
        h1, d1, w1 = transfers(eng)
        assert w1 - w0 == 3  # two sharded waves, one replica wave
        # the sharded run is one launch, the replica wave its own
        assert (h1 - h0, d1 - d0) == (2, 2)
        if wire.available():
            out = eng.check_columns(columns(reqs), now=NOW)
            assert out[2].tolist() == [997, 996, 999, 999, 998]
            h2, d2, w2 = transfers(eng)
            assert w2 - w1 == 3
            assert (h2 - h1, d2 - d1) == (2, 2)
        assert len(guarded) >= 1
        assert eng.metrics.cold_compiles == 0
    finally:
        eng.close()
