"""A columnar call whose hot key comes more than `max_waves` times stays
columnar (ISSUE 44): its waves run as further launches of the same
flush, all under one hold of the engine lock. On CPU, counts and answers
only.

- (a) the answers equal the reference's (models/oracle.py) for one key
  33, 65 and 1,000 times (the API's cap: the worst case), on the flat
  engine, with a MemoryStore, and on the CPU mesh engine's sharded
  lanes; its GLOBAL lanes, which count a replica each until the sync
  runs, equal the same engine's object path;
- (b) a flush's launches are what the rule says: 70 waves at one width
  are 3 launches and 1 upload record, and a long call whose first wave
  is wide runs its tail at the narrowest warm stacked width;
- (c) two threads sending such calls on one key: each call's answers are
  a contiguous run, the sum of hits exact;
- (d) gubernator_engine_flushes_over_max_waves moves for such flushes
  alone, and is exposed.
"""

import dataclasses
import threading

import pytest

from gubernator_tpu import wire
from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.metrics import Metrics, wire_engine_telemetry
from gubernator_tpu.models.oracle import OracleEngine
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig
from gubernator_tpu.service import pb
from gubernator_tpu.store import MemoryStore, attach_store

NOW = 1_753_700_000_000
MAX_WAVES = 32

pytestmark = pytest.mark.skipif(
    not wire.available(), reason="native wirepath unavailable"
)


def mk(key, **kw):
    kw.setdefault("duration", 60_000)
    kw.setdefault("limit", 1000)
    kw.setdefault("hits", 1)
    return RateLimitReq(name="long", unique_key=key, **kw)


def columns(reqs):
    msg = pb.pb.GetRateLimitsReq()
    for r in reqs:
        msg.requests.append(pb.req_to_pb(r))
    return wire.parse_requests(msg.SerializeToString())


def flat_engine(**kw):
    kw.setdefault("num_groups", 1 << 8)
    kw.setdefault("ways", 4)
    kw.setdefault("batch_size", 64)
    return DeviceEngine(
        EngineConfig(max_waves=MAX_WAVES, batch_wait_s=0.001, **kw),
        now_fn=lambda: NOW,
    )


def store_engine():
    eng = flat_engine()
    attach_store(eng, MemoryStore())
    return eng


def mesh_engine():
    from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig

    return IciEngine(
        IciEngineConfig(
            num_groups=256, ways=4, num_slots=512, replica_ways=4,
            batch_size=64, max_waves=MAX_WAVES, sync_wait_s=3600.0,
        ),
        now_fn=lambda: NOW,
    )


def over_max_waves(eng) -> int:
    return int(eng.metrics.flushes_over_max_waves.labels().get())


def answers(out):
    return list(zip(*(a.tolist() for a in out)))


def plain(resps):
    return [(int(r.status), r.limit, r.remaining, r.reset_time) for r in resps]


def hot_call(times: int, behavior: int = 0):
    """One key `times` times, past its limit at the end, among up to 50
    other keys (as many as the API's 1,000 items leave room for), some of
    them leaky, one of them twice."""
    others = min(50, 1000 - times)
    hot = [mk("hot", limit=times - 5, behavior=behavior)] * times
    rest = [
        mk(f"o{i % 49}", behavior=behavior, limit=7, hits=3,
           algorithm=Algorithm.LEAKY_BUCKET if i % 3 else Algorithm.TOKEN_BUCKET)
        for i in range(others)
    ]
    reqs = []
    for i in range(times):  # the others spread among the hot key's
        reqs.append(hot[i])
        if i < others:
            reqs.append(rest[i])
    return [dataclasses.replace(r) for r in reqs]


# engine: (constructor, behavior of the items, whether the reference is
# the oracle or the same engine's object path)
ENGINES = {
    "flat": (flat_engine, 0, "oracle"),
    "store": (store_engine, 0, "oracle"),
    "mesh-sharded": (mesh_engine, 0, "oracle"),
    "mesh-global": (mesh_engine, int(Behavior.GLOBAL), "object"),
}


# ---- (a) the answers ---------------------------------------------------------


@pytest.mark.parametrize("times", [33, 65, 1000])
@pytest.mark.parametrize("engine", ENGINES)
def test_answers_equal_the_reference(engine, times):
    make, behavior, reference = ENGINES[engine]
    eng, twin = make(), None
    try:
        reqs = hot_call(times, behavior)
        before = over_max_waves(eng)
        got = answers(eng.check_columns(columns(reqs), now=NOW))
        if reference == "oracle":
            oracle = OracleEngine()
            want = plain(
                oracle.decide(dataclasses.replace(r), NOW) for r in reqs)
        else:
            twin = make()
            want = plain(
                twin.check_batch([dataclasses.replace(r) for r in reqs]))
        assert got == want
        hot = [g for g, r in zip(got, reqs) if r.unique_key == "hot"]
        if reference == "oracle":
            # remaining times-6 .. 0, then OVER_LIMIT and nothing consumed
            assert [g[2] for g in hot] == list(
                range(times - 6, -1, -1)) + [0] * 5
            assert [g[0] for g in hot] == [0] * (times - 5) + [1] * 5
        rec = eng.metrics.recorder.last()
        assert rec["path"] == "columnar" and rec["n"] == len(reqs)
        assert len(rec["widths"]) == rec["waves"] >= times // (
            # a GLOBAL key's copies go round the replicas, a wave each
            eng.topo.n_dev if behavior else 1)
        if rec["waves"] > MAX_WAVES:
            assert over_max_waves(eng) == before + 1
    finally:
        eng.close()
        if twin is not None:
            twin.close()


# ---- (b) the launches ---------------------------------------------------------


def serve_uploads(eng) -> int:
    return eng.metrics.transfer_snapshot().get(
        "h2d/serve", {"count": 0})["count"]


def test_seventy_waves_at_one_width_are_three_launches_and_one_upload():
    eng = flat_engine()
    try:
        em = eng.metrics
        h0, d0, w0, u0 = em.wave_h2d, em.wave_d2h, em.waves, serve_uploads(eng)
        out = eng.check_columns(columns([mk("hot")] * 70), now=NOW)
        assert out[2].tolist() == list(range(999, 929, -1))
        assert em.waves - w0 == 70
        # 32 + 32 + 6 (padded to depth 8): one operand in, one read each
        assert (em.wave_h2d - h0, em.wave_d2h - d0) == (3, 3)
        assert serve_uploads(eng) - u0 == 1  # one crossing, before the lock
        rec = em.recorder.last()
        assert (rec["waves"], rec["launches"]) == (70, 3)
        assert rec["widths"] == [64] * 70
    finally:
        eng.close()


def test_a_long_calls_tail_runs_at_the_narrowest_warm_stacked_width():
    """200 distinct keys and one of them 70 times at batch_size 256 with
    the ladder warm: the first wave needs 256 lanes, the waves after it
    hold the repeated keys alone and run stacked at 128. One launch
    holds no more than max_waves waves, so such a call is cut by width
    too; a call that one launch holds keeps one width (the second
    call)."""
    eng = flat_engine(num_groups=1 << 12, batch_size=256, fast_buckets=True)
    try:
        assert eng.wait_warm(120)
        assert {(8, 128), (32, 128), (8, 256), (32, 256)} <= set(
            eng._warm_stacks)
        reqs = [mk(f"d{i}") for i in range(200)] + [mk("d0")] * 69
        oracle = OracleEngine()
        want = plain(oracle.decide(dataclasses.replace(r), NOW) for r in reqs)
        em = eng.metrics
        h0, d0 = em.wave_h2d, em.wave_d2h
        got = answers(eng.check_columns(columns(reqs), now=NOW))
        assert got == want
        rec = em.recorder.last()
        W = rec["waves"]
        assert W >= 70
        wide = [w for w in rec["widths"] if w == 256]
        assert 1 <= len(wide) <= 3  # the first wave, and group collisions
        assert rec["widths"] == wide + [128] * (W - len(wide))
        tail = W - len(wide)
        launches = 1 + -(-tail // MAX_WAVES)
        assert rec["launches"] == launches
        assert (em.wave_h2d - h0, em.wave_d2h - d0) == (launches, launches)

        # 30 of one key beside 200 others: one launch holds it, one width
        reqs = [mk(f"e{i}") for i in range(200)] + [mk("e0")] * 29
        got = answers(eng.check_columns(columns(reqs), now=NOW))
        assert got == plain(
            oracle.decide(dataclasses.replace(r), NOW) for r in reqs)
        rec = em.recorder.last()
        assert rec["launches"] == 1 and set(rec["widths"]) == {256}
        assert eng.metrics.cold_compiles == 0
    finally:
        eng.close()


# ---- (c) one hold of the engine lock -----------------------------------------


def test_two_threads_on_one_key_never_interleave():
    """Each call is 40 hits of one key, two launches: were the lock
    released between them, the other thread's hits could land between a
    call's 32nd and 33rd."""
    eng = flat_engine()
    rounds, per_call, limit = 12, 40, 100_000
    runs, errors = [], []

    def caller():
        try:
            for _ in range(rounds):
                out = eng.check_columns(
                    columns([mk("shared", limit=limit)] * per_call), now=NOW)
                runs.append(out[2].tolist())
        except BaseException as e:  # noqa: BLE001 - the test reads it
            errors.append(e)

    try:
        threads = [threading.Thread(target=caller) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors and len(runs) == 2 * rounds
        for run in runs:
            assert run == list(range(run[0], run[0] - per_call, -1))
        # every hit counted once: the calls' runs tile limit-1 .. limit-960
        firsts = sorted((r[0] for r in runs), reverse=True)
        assert firsts == [limit - 1 - per_call * i for i in range(2 * rounds)]
        assert over_max_waves(eng) == 2 * rounds
    finally:
        eng.close()


def test_a_failure_after_a_committed_launch_is_table_committed():
    """The second launch of a long call fails: the first has committed
    to a table that survives, so nobody may retry through another
    path."""
    from gubernator_tpu.runtime.engine import TableCommittedError

    eng = flat_engine()
    real = eng.K.decide_packed
    launches = []

    def decide(table, op, *a):
        launches.append(1)
        if len(launches) == 2:
            raise RuntimeError("the device fell over")
        return real(table, op, *a)

    try:
        eng.K = eng.K._replace(decide_packed=decide)
        with pytest.raises(TableCommittedError):
            eng.check_columns(columns([mk("hot")] * 40), now=NOW)
        eng.K = eng.K._replace(decide_packed=real)
        # the 32 hits of the launch that committed stay counted
        out = eng.check_columns(columns([mk("hot")]), now=NOW)
        assert out[2].tolist() == [1000 - 33]
    finally:
        eng.close()


# ---- (d) the counter ----------------------------------------------------------


def test_the_counter_moves_for_flushes_over_max_waves_alone():
    eng = flat_engine()
    try:
        m = Metrics()
        wire_engine_telemetry(m, eng)
        assert over_max_waves(eng) == 0
        eng.check_columns(columns([mk("a"), mk("b"), mk("b")]), now=NOW)
        eng.check_columns(columns([mk("c")] * MAX_WAVES), now=NOW)
        # the object path carries what is over max_waves to a next flush
        eng.check_batch([mk("d") for _ in range(40)])
        assert over_max_waves(eng) == 0
        eng.check_columns(columns([mk("e")] * (MAX_WAVES + 1)), now=NOW)
        assert over_max_waves(eng) == 1
        text = m.render().decode()
        assert "# TYPE gubernator_engine_flushes_over_max_waves counter" in text
        assert "\ngubernator_engine_flushes_over_max_waves 1.0\n" in text
        assert text.index("gubernator_engine_store_wave_crossings{") < (
            text.index("gubernator_engine_flushes_over_max_waves "))
    finally:
        eng.close()


def test_lanes_over_batch_size_still_need_the_object_path():
    """The lane bound stays: more distinct groups in one wave than
    batch_size has lanes."""
    eng = flat_engine(num_groups=1 << 12)
    try:
        before = eng.metrics.waves
        assert eng.check_columns(
            columns([mk(f"w{i}") for i in range(100)]), now=NOW) is None
        assert eng.metrics.waves == before and over_max_waves(eng) == 0
    finally:
        eng.close()
