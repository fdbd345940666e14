"""A flush's runs of waves are one launch each (ISSUE 35), at the level of
the engine: what `gubernator_engine_flush_launches` and
`gubernator_engine_wave_transfers` count on each path, that the answers
are those of the per-wave sequence, and what the engine observes to keep
that sequence: a Store, a pager, the replica tier's waves, a stacked
shape that is not warm."""

import jax
import numpy as np
import pytest

from gubernator_tpu import wire
from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.metrics import Metrics, wire_engine_telemetry
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig
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
    return RateLimitReq(name="se", unique_key=key, **kw)


def columns(reqs):
    msg = pb.pb.GetRateLimitsReq()
    for r in reqs:
        msg.requests.append(pb.req_to_pb(r))
    return wire.parse_requests(msg.SerializeToString())


def make_engine(**kw):
    kw.setdefault("num_groups", 1 << 8)
    kw.setdefault("ways", 4)
    kw.setdefault("batch_size", 32)
    kw.setdefault("batch_wait_s", 0.001)
    return DeviceEngine(EngineConfig(**kw), now_fn=lambda: NOW)


def counts(eng):
    """(flushes, launches, waves, operands uploaded, outputs read)."""
    em = eng.metrics
    launches = em.flush_launches.summary()
    return (launches["count"], int(launches["sum"]), em.waves,
            em.wave_h2d, em.wave_d2h)


def delta(eng, before):
    return tuple(a - b for a, b in zip(counts(eng), before))


def seven_waves():
    """Twelve keys, one of them seven times, mixed algorithms."""
    reqs = [mk(f"k{i}", algorithm=Algorithm(i % 2)) for i in range(11)]
    return reqs[:5] + [mk("dup", limit=5)] * 7 + reqs[5:]


def answers(got):
    return [(r.status, r.limit, r.remaining, r.reset_time) for r in got]


@needs_wire
def test_columnar_call_of_seven_waves_is_one_launch():
    eng = make_engine()
    try:
        before = counts(eng)
        out = eng.check_columns(columns(seven_waves()), now=NOW)
        assert out is not None
        # the hot key's seven hits in order, two of them over its limit
        assert out[2].tolist()[5:12] == [4, 3, 2, 1, 0, 0, 0]
        assert out[0].tolist()[5:12] == [0, 0, 0, 0, 0, 1, 1]
        assert delta(eng, before) == (1, 1, 7, 1, 1)
        rec = eng.metrics.recorder.snapshot()[-1]
        assert rec["waves"] == 7 and rec["widths"] == [32] * 7
        assert eng.metrics.cold_compiles == 0
    finally:
        eng.close()


@needs_wire
def test_columnar_answers_equal_the_per_wave_engines():
    """The same calls through an engine whose stacked shapes were never
    published (it launches per wave): every answer and the table equal."""
    stacked, plain = make_engine(), make_engine()
    plain._warm_stacks = ()
    try:
        for _ in range(3):
            reqs = seven_waves() + [
                mk("dup", limit=5, behavior=int(Behavior.RESET_REMAINING)),
                mk("dup", limit=5, hits=9,
                   behavior=int(Behavior.DRAIN_OVER_LIMIT)),
            ]
            b_s, b_p = counts(stacked), counts(plain)
            got = stacked.check_columns(columns(reqs), now=NOW)
            want = plain.check_columns(columns(reqs), now=NOW)
            for a, b in zip(got, want):
                assert a.tolist() == b.tolist()
            assert delta(stacked, b_s) == (1, 1, 9, 1, 1)
            assert delta(plain, b_p) == (1, 9, 9, 9, 9)
        for a, b in zip(
            jax.tree.leaves(stacked.K.to_wide(stacked.table)),
            jax.tree.leaves(plain.K.to_wide(plain.table)),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        stacked.close()
        plain.close()


def test_object_flush_of_two_widths_is_two_launches():
    """The pump narrows each wave on its own: the full first wave stays
    at batch_size, the hot key's later waves narrow to the least warm
    width, and each run of one width is one launch."""
    eng = make_engine(num_groups=1 << 10, batch_size=256, fast_buckets=True)
    plain = make_engine(num_groups=1 << 10, batch_size=256, fast_buckets=True)
    try:
        assert eng.wait_warm(300) and plain.wait_warm(300)
        assert set(eng._warm_shapes) == {128, 256}
        assert set(eng._warm_stacks) == {
            (d, b) for d in (8, 32) for b in (128, 256)
        }
        plain._warm_stacks = ()
        reqs = [mk(f"w{i}") for i in range(150)] + [mk("hot", limit=3)] * 5
        b_e, b_p = counts(eng), counts(plain)
        got, want = eng.check_batch(reqs), plain.check_batch(reqs)
        assert answers(got) == answers(want)
        assert [r.remaining for r in got[150:]] == [2, 1, 0, 0, 0]
        rec = eng.metrics.recorder.snapshot()[-1]
        assert rec["widths"] == [256, 128, 128, 128, 128]
        assert delta(eng, b_e) == (1, 2, 5, 2, 2)
        assert delta(plain, b_p) == (1, 5, 5, 5, 5)
        assert eng.metrics.cold_compiles == plain.metrics.cold_compiles == 0
    finally:
        eng.close()
        plain.close()


def _wait_for(cond, seconds=120.0):
    import time

    end = time.monotonic() + seconds
    while not cond() and time.monotonic() < end:
        time.sleep(0.05)
    return cond()


def test_ladder_stacks_are_compiled_once_wanted_and_only_narrowest():
    """A daemon that serves one wave a flush never pays for the
    ladder's stacked shapes: the warmer compiles the single-wave widths
    and parks. The first run of waves that finds no warm stacked shape
    is served per wave and wakes it; it then compiles the ladder's
    narrowest width alone (batch_size has its own from _warmup)."""
    eng = make_engine(num_groups=1 << 10, batch_size=512, fast_buckets=True)
    try:
        assert _wait_for(lambda: set(eng._warm_shapes) == {128, 256, 512})
        assert set(eng._warm_stacks) == {(8, 512), (32, 512)}
        assert eng._warm_thread.is_alive() and not eng._stack_wanted.is_set()
        reqs = [mk(f"w{i}") for i in range(150)] + [mk("hot")] * 4
        before = counts(eng)
        got = eng.check_batch(reqs)
        assert [r.remaining for r in got[150:]] == [999, 998, 997, 996]
        rec = eng.metrics.recorder.snapshot()[-1]
        assert rec["widths"] == [256, 128, 128, 128]
        assert delta(eng, before) == (1, 4, 4, 4, 4)  # per wave, no compile
        assert eng.metrics.cold_compiles == 0
        assert eng.wait_warm(300)
        assert set(eng._warm_stacks) == {
            (8, 512), (32, 512), (8, 128), (32, 128)
        }
        before = counts(eng)
        eng.check_batch(reqs)
        assert delta(eng, before) == (1, 2, 4, 2, 2)
        assert eng.metrics.cold_compiles == 0
    finally:
        eng.close()


@needs_wire
def test_columnar_call_of_several_waves_takes_a_stacked_width():
    """One wave narrows to the least warm width; several waves narrow
    only to a width whose stacked launch is warm, so they stay one
    launch: 150 keys fit 256 lanes, with a repeat they run at 512."""
    # (groups enough that the 150 keys collide in none)
    eng = make_engine(num_groups=1 << 16, batch_size=512, fast_buckets=True)
    try:
        assert eng.wait_warm(300)
        one_wave = [mk(f"c{i}") for i in range(150)]
        for reqs, widths, launches in (
            (one_wave, [256], 1),
            (one_wave + [mk("c0")] * 2, [512] * 3, 1),
            ([mk(f"d{i}") for i in range(20)] + [mk("d0")], [128] * 2, 1),
        ):
            before = counts(eng)
            assert eng.check_columns(columns(reqs), now=NOW) is not None
            rec = eng.metrics.recorder.snapshot()[-1]
            assert rec["widths"] == widths
            assert delta(eng, before)[1] == launches
        assert eng.metrics.cold_compiles == 0
    finally:
        eng.close()


def test_store_attached_engine_launches_per_wave():
    eng = make_engine()
    try:
        attach_store(eng, MemoryStore())
        before = counts(eng)
        got = eng.check_batch([mk("s")] * 4 + [mk("t")])
        assert [r.remaining for r in got] == [999, 998, 997, 996, 999]
        assert delta(eng, before) == (1, 4, 4, 4, 4)
    finally:
        eng.close()


def test_paged_engine_launches_per_wave_and_warms_no_stacked_shape():
    eng = make_engine(
        num_groups=64, page_groups=8, page_budget=4,
        page_demote_interval_s=0,
    )
    try:
        assert eng._warm_stacks == ()
        before = counts(eng)
        got = eng.check_batch([mk("p")] * 3)
        assert [r.remaining for r in got] == [999, 998, 997]
        assert delta(eng, before) == (1, 3, 3, 3, 3)
    finally:
        eng.close()


@pytest.mark.parametrize("warm, launches", [
    (((8, 32), (32, 32)), 1),   # 9 waves: the least depth that holds them
    (((8, 32),), 2),            # only a depth too shallow: 8 waves + 1
    (((32, 128),), 9),          # only another width: per wave
    ((), 9),
])
def test_a_stacked_shape_outside_the_warm_set_is_not_used(warm, launches):
    """The serving path takes a stacked shape only from the published
    set; otherwise it launches per wave, and nothing compiles."""
    eng = make_engine()
    try:
        assert set(eng._warm_stacks) == {(8, 32), (32, 32)}
        eng._warm_stacks = warm
        before = counts(eng)
        got = eng.check_batch([mk("n")] * 9)
        assert [r.remaining for r in got] == list(range(999, 990, -1))
        assert delta(eng, before) == (1, launches, 9, launches, launches)
        assert eng.metrics.cold_compiles == 0
    finally:
        eng.close()


def test_flush_launches_is_exposed_after_flush_waves():
    eng = make_engine()
    try:
        m = Metrics()
        wire_engine_telemetry(m, eng)
        eng.check_batch([mk("x")] * 6)
        names = [ln.split("{")[0].split(" ")[0]
                 for ln in m.render().decode().splitlines()
                 if ln.startswith("gubernator_engine_")]
        order = list(dict.fromkeys(
            n.rsplit("_", 1)[0] if n.endswith(("_sum", "_count", "_bucket"))
            else n for n in names
        ))
        i = order.index("gubernator_engine_flush_waves")
        assert order[i + 1:i + 3] == [
            "gubernator_engine_wave_transfers",
            "gubernator_engine_flush_launches",
        ]
        value = {
            ln.split(" ")[0]: float(ln.split(" ")[1])
            for ln in m.render().decode().splitlines()
            if ln.startswith("gubernator_engine_flush_") and "{" not in ln
        }
        assert value["gubernator_engine_flush_launches_sum"] == 1.0
        assert value["gubernator_engine_flush_launches_count"] == 1.0
        assert value["gubernator_engine_flush_waves_sum"] == 6.0
    finally:
        eng.close()


def test_benchmark_reader_of_launches_per_flush():
    """The benchmark's data-file reader finds the histogram under the
    names it asks /metrics for: launches a flush between two scrapes;
    nothing (and no error) on a program without the histogram, as the
    parent commit is."""
    import os

    from benchmarks import manifest, readers

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    man = manifest.load(root)
    entry = {p["name"]: p for p in man["per_layer"]}["launches_per_flush"]
    names = [p["name"] for p in man["per_layer"]]
    # appended by PR 35 after PR 32's last, and nothing moved since
    assert names.index("launches_per_flush") == names.index("shard_imbalance") + 1
    assert entry["layer"] == "engine host stage"
    assert entry["moves"] == "decisions_per_s" and entry["better"] == "lower"
    assert entry["workloads"] == next(
        e["workloads"] for e in man["end_to_end"]
        if e["name"] == "decisions_per_s"
    )
    path = manifest.reader_path(
        root, manifest.bench_dir(man), "launches_per_flush"
    )
    assert path.endswith(".json")  # data, no reader code

    def scrape(m):
        series = {}
        for line in m.render().decode().splitlines():
            name, _, value = line.rpartition(" ")
            if name and not line.startswith("#"):
                series[name] = float(value)
        return series

    def read(before, after):
        return readers.read(path, readers.Context(
            before=before, after=after, device={}, phases={}, generator={},
            trace=None, conf={}, traffic={}, table={}, items_answered=0,
            root=root,
        ))

    eng = make_engine()
    try:
        m = Metrics()
        wire_engine_telemetry(m, eng)
        before = scrape(m)
        eng.check_batch([mk("r")] * 7)   # seven waves, one launch
        eng.check_batch([mk("s")])       # one wave, one launch
        after = scrape(m)
        assert read(before, after) == pytest.approx(1.0)
        assert read(after, after) is None  # no flush between the scrapes
        parent = {k: v for k, v in after.items() if "flush_launches" not in k}
        assert read(parent, parent) is None
    finally:
        eng.close()


def test_mesh_engine_stacks_sharded_waves_and_not_replica_waves():
    """The pod daemon's engine on faked devices: the owner-sharded run
    is one launch of the stacked SPMD program, the replica tier's GLOBAL
    waves stay one launch each; answers equal a one-device engine's for
    the sharded keys."""
    from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig

    eng = IciEngine(
        IciEngineConfig(
            num_groups=256, ways=4, num_slots=512, replica_ways=4,
            batch_size=32, sync_wait_s=3600.0,
        ),
        now_fn=lambda: NOW,
    )
    one = make_engine()
    try:
        assert set(eng._warm_stacks) == {(8, 32), (32, 32)}
        sharded = seven_waves()
        before = counts(eng)
        got = eng.check_batch(sharded)
        assert answers(got) == answers(one.check_batch(sharded))
        assert delta(eng, before) == (1, 1, 7, 1, 1)
        g = [mk("g", behavior=int(Behavior.GLOBAL))] * 3
        n_dev = eng.topo.n_dev
        before = counts(eng)
        got = eng.check_batch(sharded[:7] + g)
        # the GLOBAL items land on consecutive replicas: one wave while
        # there are as many devices as items
        r_waves = -(-3 // n_dev)
        assert delta(eng, before) == (
            1, 1 + r_waves, 2 + r_waves, 1 + r_waves, 1 + r_waves
        )
        if wire.available():
            before = counts(eng)
            out = eng.check_columns(columns(sharded), now=NOW)
            assert out is not None
            assert delta(eng, before) == (1, 1, 7, 1, 1)
        assert eng.metrics.cold_compiles == 0
    finally:
        eng.close()
        one.close()
