"""The owner-sharded tier counts what each shard answered
(gubernator_shard_decisions{shard}) and answers as one limiter would.

Four faked devices (a v5e host's mesh; tests/conftest.py forces eight):
a seeded skewed batch through check_columns and through the object
path gives per-shard deltas equal to `group // groups_per_shard` counted
by hand, their sum equals the lanes answered, GLOBAL lanes (the replica
tier's) are not counted, and the series are on /metrics. And the sharded
daemon, served over gRPC, gives the benchmark's plain reference's answers
for a seeded plan in which one key comes more often in a call than there
are shards. Counts only: no time is compared with anything."""

import random
import re
import time

import jax
import numpy as np
import pytest

from benchmarks import wire as bench_wire
from benchmarks.reference.oracle import Reference, Request
from gubernator_tpu import native, wire
from gubernator_tpu.api.types import Behavior, RateLimitReq
from gubernator_tpu.metrics import Metrics, wire_engine_telemetry
from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig
from gubernator_tpu.service import pb
from gubernator_tpu.service.config import DaemonConfig
from gubernator_tpu.service.daemon import Daemon

NOW = 1_753_700_000_000
N_DEV = 4
NUM_GROUPS = 256

needs_wire = pytest.mark.skipif(
    not wire.available(), reason="native wirepath unavailable"
)


def mk(key, behavior=0):
    return RateLimitReq(name="sd", unique_key=key, hits=1, limit=1000,
                        duration=60_000, behavior=behavior)


def columns(reqs):
    msg = pb.pb.GetRateLimitsReq()
    for r in reqs:
        msg.requests.append(pb.req_to_pb(r))
    return wire.parse_requests(msg.SerializeToString())


def skewed_batch(seed, n=60, keys=25):
    """`n` requests over `keys` keys, the first drawn ~7 times as often
    as any other: several waves, an uneven split over the shards."""
    rng = random.Random(seed)
    return [mk(f"k{0 if rng.random() < 0.2 else rng.randrange(keys)}")
            for _ in range(n)]


def by_hand(reqs):
    """Lanes a shard owns: group // groups per shard, from the hash the
    columnar edge computes."""
    cols = columns(reqs)
    _hi, _lo, grp = native.hash128_batch_raw(
        cols.key_data.tobytes(), cols.key_offsets, NUM_GROUPS)
    return np.bincount(np.asarray(grp) // (NUM_GROUPS // N_DEV),
                       minlength=N_DEV).tolist()


@pytest.fixture
def engine():
    eng = IciEngine(
        IciEngineConfig(devices=jax.devices()[:N_DEV], num_groups=NUM_GROUPS,
                        num_slots=2048, batch_size=32, batch_wait_s=0.001,
                        sync_wait_s=3600.0),
        now_fn=lambda: NOW,
    )
    yield eng
    eng.close()


def decisions(eng):
    return list(eng.shard_stats()["decisions"])


def through_columns(eng, reqs):
    out = eng.check_columns(columns(reqs), now=NOW)
    assert out is not None
    return len(out[0])


def through_objects(eng, reqs):
    return len(eng.check_batch(reqs))


PATHS = {
    "columnar": pytest.param(through_columns, marks=needs_wire),
    "object": pytest.param(through_objects, marks=needs_wire),
}


@pytest.mark.parametrize("path", PATHS.values(), ids=PATHS.keys())
def test_per_shard_deltas_equal_the_groups_counted_by_hand(engine, path):
    reqs = skewed_batch(seed=32)
    want = by_hand(reqs)
    assert max(want) > min(want) and sum(want) == len(reqs)
    for _ in range(2):  # a delta, not a level: the second pass adds as much
        before = decisions(engine)
        answered = path(engine, reqs)
        delta = [a - b for a, b in zip(decisions(engine), before)]
        assert delta == want
        assert sum(delta) == answered == len(reqs)


@needs_wire
def test_global_lanes_go_to_the_replica_tier_and_are_not_counted(engine):
    plain = skewed_batch(seed=7, n=20)
    mixed = plain + [mk(f"g{i}", behavior=int(Behavior.GLOBAL)) for i in range(9)]
    before = decisions(engine)
    assert through_columns(engine, mixed) == len(mixed)
    delta = [a - b for a, b in zip(decisions(engine), before)]
    assert delta == by_hand(plain) and sum(delta) == len(plain)


@needs_wire
def test_one_series_a_shard_on_metrics_and_their_sum_is_the_lanes(engine):
    m = Metrics()
    wire_engine_telemetry(m, engine)
    reqs = skewed_batch(seed=3)
    through_columns(engine, reqs)
    through_objects(engine, reqs)
    text = m.render().decode()
    series = {}
    for line in text.splitlines():
        if line.startswith("gubernator_shard_decisions{"):
            name, _, value = line.rpartition(" ")
            series[name] = float(value)
    assert sorted(series) == [
        f'gubernator_shard_decisions{{shard="{i}"}}' for i in range(N_DEV)]
    assert [series[k] for k in sorted(series)] == [2 * n for n in by_hand(reqs)]
    assert "# TYPE gubernator_shard_decisions counter" in text


def test_a_single_device_engine_has_no_series():
    from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig

    eng = DeviceEngine(EngineConfig(num_groups=64, ways=4, batch_size=32),
                       now_fn=lambda: NOW)
    try:
        m = Metrics()
        wire_engine_telemetry(m, eng)
        eng.check_batch([mk("a"), mk("b")])
        assert "gubernator_shard_decisions{" not in m.render().decode()
    finally:
        eng.close()


# ---- the sharded daemon, served ------------------------------------------------


@pytest.fixture(scope="module")
def sharded_daemon(loop_thread):
    conf = DaemonConfig(
        global_mode="ici",
        ici=IciEngineConfig(devices=jax.devices()[:N_DEV], num_groups=1 << 9,
                            num_slots=1 << 11, batch_size=64,
                            batch_wait_s=0.002, sync_wait_s=0.05),
    )
    d = loop_thread.run(Daemon.spawn(conf), timeout=180)
    yield d
    loop_thread.run(d.close())


@needs_wire
def test_the_sharded_daemon_served_gives_the_reference_answers(sharded_daemon):
    """24 calls of 40 items, limit 100 an hour, pinned clock: the hot key
    comes 9 times a call, more often than there are shards (so its waves
    outnumber the owners and every one lands on the same chip), and
    passes its limit in the twelfth call; 60 other keys fall on all four
    shards."""
    rng = np.random.default_rng([32, 4])
    ref = Reference()
    # pinned, but near the server's own clock, which decides what has expired
    t_pin = int(time.time() * 1000)
    channel, stub = bench_wire.open_channel(sharded_daemon.grpc_address)
    before = decisions(sharded_daemon.engine)
    lanes = over = 0
    try:
        for call in range(24):
            now = t_pin + 10 * call
            ids = np.concatenate([np.zeros(9, np.int64),
                                  rng.integers(1, 61, size=31)])
            rng.shuffle(ids)
            reqs = [Request(name="served", unique_key=f"key{k:03d}", hits=1,
                            limit=100, duration=3_600_000, created_at=now)
                    for k in ids]
            got = bench_wire.decode_call(stub(bench_wire.encode_call(reqs),
                                              timeout=60))
            want = [r.as_tuple() for r in ref.get_rate_limits(reqs, now)]
            assert [tuple(g) for g in got] == want, f"call {call}"
            lanes += len(reqs)
            over += sum(1 for g in got if g[0] == 1)
    finally:
        channel.close()
    assert over == 24 * 9 - 100  # OVER_LIMIT never consumed, none came early
    after = decisions(sharded_daemon.engine)
    delta = [a - b for a, b in zip(after, before)]
    assert sum(delta) == lanes and all(d > 0 for d in delta)


# ---- the sharded decide's phases, and the tool that splits a capture by them ----

PHASES = ("owner_mask", "decide", "psum_merge")


def test_the_sharded_decide_names_its_three_phases():
    """jax.named_scope puts the phase into every operation's op_name path;
    the profiler hands that path back with each device event."""
    from gubernator_tpu.ops.layout import WaveOperand
    from gubernator_tpu.parallel import mesh as pmesh

    mesh = pmesh.make_mesh(jax.devices()[:N_DEV])
    table = pmesh.create_sharded_table(mesh, NUM_GROUPS, 8)
    fn = pmesh.make_sharded_decide(mesh, NUM_GROUPS, 8)
    text = fn.lower(table, WaveOperand.zeros(32).stamp(NOW).buf,
                    with_store=False).as_text(debug_info=True)
    for phase in PHASES:
        assert f'loc("{phase}/' in text, phase


def test_trace_phases_splits_a_programs_device_time_by_phase():
    from tools import trace_phases

    lines = {
        "XLA Modules": [(100, 50, ["jit_decide_fn(7)"]), (200, 50, ["jit_sync_fn(9)"]),
                        (300, 40, ["jit_decide_fn(7)"])],
        "XLA Ops": [
            (100, 5, ["%fusion.1", "jit(decide_fn)/shmap/owner_mask/sub"]),
            (110, 20, ["%fusion.5", "jit(decide_fn)/shmap/decide/scatter/add"]),
            (135, 4, ["%all-reduce", "jit(decide_fn)/shmap/psum_merge/psum"]),
            (140, 6, ["%while.3", ""]),  # the compiler named no scope
            (210, 30, ["%fusion.9", "jit(sync_fn)/decide/x"]),  # another program's
            (305, 10, ["%fusion.5", "jit(decide_fn)/shmap/decide/scatter/add"]),
        ],
    }
    got = trace_phases.reduce_plane(lines, re.compile("decide_fn"), PHASES)
    assert got["executions"] == 2 and got["program_s"] == pytest.approx(90e-9)
    assert {k: round(v * 1e9) for k, v in got["phases"].items()} == {
        "owner_mask": 5, "decide": 30, "psum_merge": 4, trace_phases.NONE: 6}
    # a phase is a whole component of the path, not a substring of one
    assert trace_phases.phase_of(["jit(decide_fn)/decided/x"], PHASES) == trace_phases.NONE
