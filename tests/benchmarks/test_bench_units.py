"""Unit tests of the benchmark's yardstick (no server, no chip): the
arithmetic, the traffic generator, the manifest check, the comparison that
decides ``correct``, the trace reduction and the roofline bytes."""

import copy
import json
import math
import os
import random
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import (  # noqa: E402
    check,
    manifest,
    readers,
    roofline,
    stats,
    trace_reduce,
    traffic,
    wire,
)
from benchmarks.reference import oracle as ref  # noqa: E402

KS = traffic.Keyspace(name="t", n=1000, limit=10, duration_ms=5000,
                      algorithm="token", behavior=0, salt=7)


# ---- stats ---------------------------------------------------------------------


@pytest.mark.parametrize("q,want", [(50, 50), (99, 99), (100, 100), (1, 1), (0.5, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile(range(1, 101), q) == want


def test_percentile_of_a_shuffled_sample_and_beyond():
    xs = list(range(1, 1001))
    random.Random(3).shuffle(xs)
    assert stats.percentile(xs, 99) == 990
    assert stats.beyond(1000, 99) == 10  # ten calls lie beyond a p99 of 1,000
    assert stats.beyond(999, 99) == 9
    with pytest.raises(ValueError):
        stats.percentile([], 50)


# ---- traffic ----------------------------------------------------------------------


def test_due_times_same_gaps_for_every_seed_in_another_order():
    a = traffic.due_times({"kind": "poisson"}, 120.0, 20.0, traffic.rng_for(1, 3))
    b = traffic.due_times({"kind": "poisson"}, 120.0, 20.0, traffic.rng_for(2**31 + 5, 3))
    assert len(a) == len(b) == 2400
    assert np.all(np.diff(a) > 0) and a[0] >= 0 and a[-1] < 20.0
    ga, gb = np.sort(np.diff(a)), np.sort(np.diff(b))
    # one gap differs: the first call's, which is not a difference of two
    assert np.allclose(ga[5:-5], gb[5:-5], rtol=0, atol=1e-3)
    assert not np.allclose(np.diff(a), np.diff(b))
    # exponential gaps: mean 1/rate, standard deviation about the mean
    gaps = np.diff(a)
    assert abs(gaps.mean() - 1 / 120.0) < 2e-4
    assert 0.9 < gaps.std() / gaps.mean() < 1.1


def test_due_times_bursts():
    d = traffic.due_times({"kind": "bursts", "calls": 50, "every_ms": 100}, 500.0, 2.0,
                          traffic.rng_for(1, 3))
    assert len(d) == 1000 and d[0] == 0 and d[49] == 0 and d[50] == pytest.approx(0.1)


def test_zipf_head_mass_is_one_over_harmonic():
    n, s = 100_000, 0.99
    h = sum(r ** -s for r in range(1, n + 1))
    keys = traffic.draw_keys({"distribution": "zipf", "s": s}, n, 400_000,
                             traffic.rng_for(5, 1))
    share = np.mean(keys == 0)
    assert abs(share - 1 / h) < 0.1 / h
    assert abs(np.mean(keys == 1) - 2 ** -s / h) < 0.1 / h


def test_scrambled_zipf_spreads_the_head_and_names_the_hottest():
    n = 50_000
    spec = {"distribution": "zipf", "s": 0.99, "scrambled": True}
    keys = traffic.draw_keys(spec, n, 200_000, traffic.rng_for(9, 1))
    ids, counts = np.unique(keys, return_counts=True)
    hottest = traffic.hottest_keys(spec, n, 3)
    assert ids[np.argmax(counts)] == hottest[0]
    assert hottest[0] != 0 and 0 <= hottest.min() and hottest.max() < n
    assert np.array_equal(traffic.hottest_keys(spec, n, 3), hottest)  # fixed mix


def test_uniform_and_hotset_draws():
    u = traffic.draw_keys({"distribution": "uniform"}, 100, 50_000, traffic.rng_for(1, 1))
    assert u.min() == 0 and u.max() == 99
    hs = traffic.draw_keys({"distribution": "hotset", "hot_keys": 10, "hot_share": 0.9},
                           1000, 50_000, traffic.rng_for(1, 1))
    assert abs(np.mean(hs < 10) - 0.9) < 0.01
    with pytest.raises(ValueError):
        traffic.draw_keys({"distribution": "nope"}, 10, 10, traffic.rng_for(1, 1))


def test_call_sizes_exact_proportions_whatever_the_seed():
    spec = {"2": 0.7, "100": 0.25, "1000": 0.05}
    for seed in (1, 2**31 + 11):
        sizes = traffic.call_sizes(spec, 1000, traffic.rng_for(seed, 2))
        vals, counts = np.unique(sizes, return_counts=True)
        assert dict(zip(vals.tolist(), counts.tolist())) == {2: 700, 100: 250, 1000: 50}
    assert traffic.apportion({1: 1, 2: 1, 3: 1}, 10) in (
        [(1, 4), (2, 3), (3, 3)], [(1, 3), (2, 4), (3, 3)], [(1, 3), (2, 3), (3, 4)])
    with pytest.raises(ValueError):
        traffic.call_sizes(1001, 5, traffic.rng_for(1, 2))


def test_plan_is_a_function_of_the_seed_and_takes_large_seeds():
    conf = {"keyspace": {"keys": 500, "limit": 10, "duration_ms": 5000,
                         "algorithm": "even_token_odd_leaky"}}
    traf = {"loop": "closed", "callers": 4, "pool_calls": 5, "items_per_call": 2,
            "keys": {"distribution": "uniform"}}
    seed = 2**31 + 12345
    ks = traffic.Keyspace.from_config(conf, seed)
    p1 = traffic.build_plan(traf, ks, seed, 5.0)
    p2 = traffic.build_plan(traf, traffic.Keyspace.from_config(conf, seed), seed, 5.0)
    assert p1.blobs == p2.blobs and len(p1.blobs) == 20
    assert sorted(set(p1.caller_of.tolist())) == [0, 1, 2, 3]
    p3 = traffic.build_plan(traf, traffic.Keyspace.from_config(conf, seed + 1), seed + 1, 5.0)
    assert p3.blobs != p1.blobs
    assert ks.algorithm_of(2) == ref.TOKEN_BUCKET and ks.algorithm_of(3) == ref.LEAKY_BUCKET
    behav = dict(traf, behavior_shares=[{"share": 0.9, "behavior": []},
                                        {"share": 0.1, "behavior": ["RESET_REMAINING"]}])
    p4 = traffic.build_plan(behav, ks, seed, 5.0)
    flat = np.concatenate(p4.behaviors)
    assert np.sum(flat == 8) == 4 and np.sum(flat == 0) == 36


def test_open_plan_holds_rate_times_seconds_calls():
    conf = {"keyspace": {"keys": 500, "limit": 10, "duration_ms": 5000, "algorithm": "token"}}
    traf = {"loop": "open", "rate_calls_per_s": 120.0, "items_per_call": 2}
    ks = traffic.Keyspace.from_config(conf, 1)
    p = traffic.build_plan(traf, ks, 1, 10.0)
    assert len(p.blobs) == len(p.due) == 1200


# ---- the wire stub against the program's own messages --------------------------------


def test_wire_stub_speaks_the_programs_proto():
    from gubernator_tpu.service import pb

    items = [ref.Request(name="n", unique_key="k1", hits=1, limit=10, duration=5000,
                         algorithm=1, behavior=2, burst=3, created_at=1234),
             ref.Request(name="n", unique_key="k2", hits=0, limit=7, duration=9)]
    theirs = pb.pb.GetRateLimitsReq.FromString(wire.encode_call(items))
    assert [(r.name, r.unique_key, r.hits, r.limit, r.duration, r.algorithm, r.behavior,
             r.burst) for r in theirs.requests] == [
        ("n", "k1", 1, 10, 5000, 1, 2, 3), ("n", "k2", 0, 7, 9, 0, 0, 0)]
    assert theirs.requests[0].HasField("created_at") and theirs.requests[0].created_at == 1234
    assert not theirs.requests[1].HasField("created_at")
    resp = pb.pb.GetRateLimitsResp()
    r = resp.responses.add()
    r.status, r.limit, r.remaining, r.reset_time, r.error = 1, 10, 0, 99, "e"
    assert wire.decode_call(resp.SerializeToString()) == [(1, 10, 0, 99, "e")]


# ---- the reference against models/oracle.py ----------------------------------------------


def test_reference_agrees_with_the_programs_oracle_on_a_seeded_replay():
    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.models.oracle import OracleEngine

    rng = random.Random(20230923)
    mine, theirs = ref.Reference(), OracleEngine()
    now = 1_700_000_000_000
    behaviors = [0, 0, 0, ref.RESET_REMAINING, ref.DRAIN_OVER_LIMIT]
    for step in range(4000):
        now += rng.choice([0, 1, 50, 700, 6000])
        kw = dict(name="r", unique_key=f"k{rng.randrange(40)}", hits=rng.randrange(0, 5),
                  limit=rng.choice([1, 5, 10]), duration=rng.choice([1000, 5000]),
                  algorithm=rng.randrange(2), behavior=rng.choice(behaviors),
                  burst=rng.choice([0, 0, 7]), created_at=now)
        a = mine.get_rate_limits([ref.Request(**kw)], now)[0]
        b = theirs.get_rate_limits([RateLimitReq(**kw)], now)[0]
        assert a.as_tuple() == (int(b.status), b.limit, b.remaining, b.reset_time, b.error), step


def test_reference_refuses_what_it_does_not_model():
    r = ref.Reference().get_rate_limits(
        [ref.Request(name="a", unique_key="b", behavior=ref.DURATION_IS_GREGORIAN)], 0)[0]
    assert "outside the reference" in r.error
    assert ref.Reference().get_rate_limits([ref.Request(name="a")], 0)[0].error


# ---- the comparison ------------------------------------------------------------------


def items_of(rows, behavior=0):
    """rows: (key, status, remaining, reset_time)"""
    a = np.asarray(rows, dtype=np.int64).reshape(-1, 4)
    return check.Items(key=a[:, 0], status=a[:, 1], limit=np.full(len(a), KS.limit),
                       remaining=a[:, 2], reset_time=a[:, 3],
                       valid=np.ones(len(a), bool), behavior=np.full(len(a), behavior))


def window(rows, carried=None):
    wc = check.WindowCheck(KS, carried or check.Carried.empty(KS.n),
                           np.zeros(KS.n, bool))
    v = check.Verdict()
    wc.check_window(items_of(rows), v)
    return wc, v


R = 1_000_000  # a reset_time


def test_window_accepts_a_sound_generation_in_any_order():
    _, v = window([(5, 0, 7, R), (5, 0, 9, R), (5, 0, 8, R), (6, 0, 9, R + 1)])
    assert v.correct, v.lines()


def test_window_catches_a_skipped_remaining():
    _, v = window([(5, 0, 9, R), (5, 0, 7, R)])  # 8 never answered: a hit counted twice
    assert not v.correct
    assert dict((n, x) for n, x, _ in v.rows)["window.token_generations_not_exact"] == 1


def test_window_catches_a_doubled_remaining():
    _, v = window([(5, 0, 9, R), (5, 0, 8, R), (5, 0, 8, R)])  # a hit not counted
    assert not v.correct


def test_window_catches_over_limit_that_consumed_or_came_early():
    _, v = window([(5, 0, 9, R), (5, 1, 0, R)])  # OVER_LIMIT with 9 left
    assert dict((n, x) for n, x, _ in v.rows)["window.over_limit_before_used_up"] == 1
    rows = [(5, 0, r, R) for r in range(9, -1, -1)] + [(5, 1, 0, R), (5, 1, 0, R)]
    assert window(rows)[1].correct
    _, v = window([(5, 1, 3, R)])  # OVER_LIMIT carrying a remaining
    assert not v.correct


def test_window_catches_limit_not_echoed_and_out_of_range():
    it = items_of([(5, 0, 9, R)])
    it.limit[0] = 11
    v = check.Verdict()
    check.WindowCheck(KS, check.Carried.empty(KS.n),
                      np.zeros(KS.n, bool)).check_window(it, v)
    assert not v.correct
    assert not window([(5, 0, 11, R)])[1].correct
    assert not window([(5, 0, -1, R)])[1].correct


def test_window_starts_a_carried_generation_where_the_reference_left_it():
    c = check.Carried.empty(KS.n)
    c.remaining[5], c.reset_time[5] = 6, R
    assert window([(5, 0, 5, R), (5, 0, 4, R)], c)[1].correct
    assert not window([(5, 0, 9, R)], c)[1].correct  # the server forgot 4 hits


def test_window_counts_a_generation_made_while_the_old_one_lived_as_eviction():
    wc, v = window([(5, 0, 9, R), (5, 0, 9, R + 100)])  # 100 ms later, duration 5 s
    assert v.correct and wc.evicted == {5}
    wc, v = window([(5, 0, 9, R), (5, 0, 9, R + 5001)])  # after expiry: not an eviction
    assert v.correct and wc.evicted == set()
    v = check.Verdict()
    wc.evicted = {1, 2, 3, 4}
    wc.check_evictions(1000, 8192, 8, v)  # every key looked at, no group over-full: limit 3
    assert not v.correct
    wc.evicted = {1, 2}
    v = check.Verdict()
    wc.check_evictions(1000, 8192, 8, v)
    assert v.correct


def test_a_failed_calls_keys_are_held_to_ranges_only():
    unc = np.zeros(KS.n, bool)
    unc[5] = True
    wc = check.WindowCheck(KS, check.Carried.empty(KS.n), unc)
    v = check.Verdict()
    wc.check_window(items_of([(5, 0, 9, R), (5, 0, 7, R)]), v)  # a gap: the lost call's hit
    assert v.correct
    v = check.Verdict()
    wc.check_window(items_of([(5, 0, 9, R), (5, 0, 9, R)]), v)  # a repeat is still wrong
    assert not v.correct


def test_probes_equal_what_was_left():
    wc, v = window([(5, 0, 9, R), (5, 0, 8, R)])
    good = items_of([(5, 0, 8, R), (6, 0, 10, R + 9000)])
    wc.check_probes(good, v)
    assert v.correct, v.lines()
    v2 = check.Verdict()
    wc.check_probes(items_of([(5, 0, 9, R)]), v2)  # a hit was lost
    assert not v2.correct
    # sticky: a generation that answered OVER_LIMIT keeps that status on a probe
    rows = [(7, 0, r, R) for r in range(9, -1, -1)] + [(7, 1, 0, R)]
    wc, v = window(rows)
    wc.check_probes(items_of([(7, 1, 0, R)]), v)
    assert v.correct, v.lines()
    v3 = check.Verdict()
    wc.check_probes(items_of([(7, 0, 0, R)]), v3)
    assert not v3.correct


def test_sequential_compares_and_adopts_an_eviction():
    served = ref.Reference()
    now = 5_000

    def send(reqs):
        return [r.as_tuple() for r in served.get_rate_limits(copy.deepcopy(reqs), now)]

    seq = check.Sequential(send)
    r1 = KS.request(3, 1, created_at=now)
    seq.call("a", [3], [r1], now)
    seq.call("b", [3], [KS.request(3, 1, created_at=now)], now)
    assert seq.mismatches == 0 and seq.items == 2
    del served.cache[r1.hash_key()]  # the table evicts the key
    seq.call("c", [3], [KS.request(3, 1, created_at=now)], now)
    assert seq.mismatches == 0 and seq.evicted == {3}
    assert seq.token_state(3, KS) == (9, now + 5000, False)
    served.cache[r1.hash_key()].value.remaining = 4  # a wrong count is no eviction
    seq.call("d", [3], [KS.request(3, 1, created_at=now)], now)
    assert seq.mismatches == 1


def test_lost_share_is_the_poisson_overflow():
    m = 1_000_000 / 262_144
    want = sum((k - 8) * math.exp(-m) * m ** k / math.factorial(k)
               for k in range(9, 60)) / m
    assert check.lost_share(1_000_000, 262_144, 8) == pytest.approx(want)
    # PR 21's census on the chip: 993,340 of 1,000,000 keys resident
    assert want == pytest.approx(6_660 / 1_000_000, rel=0.02)
    assert check.lost_share(10, 8192, 8) < 1e-12


def test_evictable_share_is_the_poisson_tail():
    m = 1_000_000 / 262_144
    want = 1 - sum(math.exp(-m) * m ** k / math.factorial(k) for k in range(8))
    assert check.evictable_share(1_000_000, 262_144, 8) == pytest.approx(want)
    assert 0.040 < want < 0.042
    assert check.evictable_share(10, 8192, 8) < 1e-9


@pytest.mark.parametrize("observed,keys,groups,want", [
    (0, 10_000, 8192, 2),  # nothing observed
    (10_000, 10_000, 8192, 5),  # batching-10k: 0.2 keys in over-full groups + 4 sd
    (61_000, 1_000_000, 262_144, 1286),  # zipf-1m on the chip: 3 x 428 expected
    (19_200, 20_000, 4096, 2515),  # its rehearsal: every key looked at, the cap
])
def test_eviction_allowance(observed, keys, groups, want):
    assert check.eviction_allowance(observed, keys, groups, 8) == want


# ---- manifest -------------------------------------------------------------------------


def repo_manifest():
    return manifest.load(ROOT)


def test_the_repos_manifest_passes():
    manifest.check(repo_manifest(), ROOT)


def broken(edit):
    m = copy.deepcopy(repo_manifest())
    edit(m)
    with pytest.raises(manifest.ManifestError):
        manifest.check(m, ROOT)


@pytest.mark.parametrize("edit", [
    lambda m: m["workloads"][0].update(name="bad name"),
    lambda m: m["end_to_end"][0].update(unit="decisions per second"),
    lambda m: m["end_to_end"][0].update(unit="x" * 17),
    lambda m: m["configs"][0].update(source="s" * 201),
    lambda m: [w.update(chips=4) for w in m["workloads"]],
    lambda m: m["configs"].append(dict(m["configs"][0], name="orphan",
                                       file="benchmarks/traffic/herd.json")),
    lambda m: m["per_layer"][0].update(workloads=[m["workloads"][0]["name"]],
                                       moves="call_p50_ms"),
    lambda m: m["per_layer"][0].update(name="no_reader_file"),
    lambda m: m["workloads"][0].update(traffic="no_such_traffic"),
    lambda m: m["end_to_end"][0].update(bound=0.3),
    lambda m: m.update(run_seconds=52),
    lambda m: m["end_to_end"][0].update(why="x"),
    lambda m: m["workloads"].append(dict(m["workloads"][0], name="twice")),
    lambda m: m["command"].append("/etc/passwd\n"),
    lambda m: m["paths"].append("../elsewhere"),
], ids=["name", "unit-space", "unit-long", "source-long", "too-many-four-chip",
        "config-without-cell", "moves-not-reported", "no-reader", "no-traffic",
        "bound", "run-seconds", "extra-key", "pair-twice", "command-line", "path-out"])
def test_manifest_check_refuses(edit):
    broken(edit)


def test_metrics_of_a_cell():
    m = repo_manifest()
    e2e = {x["name"] for x in manifest.metrics_of(m, "batching-10k.steady", "end_to_end")}
    assert e2e == {"call_p50_ms", "setup_s"}
    herd = {x["name"] for x in manifest.metrics_of(m, "batching-10k.herd", "per_layer")}
    assert "fast_path_share" in herd and "gen_late_p99_ms" not in herd
    assert "compile_s" in herd  # no `workloads` key: every cell that reports setup_s


# ---- readers --------------------------------------------------------------------------


def ctx(**kw):
    base = dict(before={}, after={}, device={}, phases={}, generator={}, trace=None,
                conf={}, traffic={}, table={"ways": 8}, items_answered=0, root=ROOT)
    base.update(kw)
    return readers.Context(**base)


def test_metrics_ratio_reader_takes_deltas():
    c = ctx(before={"a_sum": 1.0, "a_count": 10.0, "b_sum": 0.5},
            after={"a_sum": 3.0, "a_count": 20.0, "b_sum": 1.0})
    spec = {"kind": "metrics_ratio", "plus": ["a_sum"], "minus": ["b_sum"],
            "per": ["a_count"], "scale": 1000.0}
    assert readers.KINDS["metrics_ratio"](spec, c) == pytest.approx(150.0)
    assert readers.KINDS["metrics_ratio"](dict(spec, plus=["absent"]), c) is None
    assert readers.KINDS["metrics_ratio"](
        dict(spec, per=["b_sum"]), ctx(before={"b_sum": 1.0}, after={"b_sum": 1.0})) is None


def test_every_per_layer_metric_has_a_reader_that_returns_nothing_on_nothing():
    m = repo_manifest()
    for p in m["per_layer"]:
        path = manifest.reader_path(ROOT, manifest.bench_dir(m), p["name"])
        assert readers.read(path, ctx()) is None, p["name"]


# ---- roofline ---------------------------------------------------------------------------


def test_decide_bytes_by_hand():
    # one lane: 8 ways x 80 B read + 80 B written + 8 request and 4 response columns of 8 B
    assert roofline.decide_bytes(1, 80) == 8 * 80 + 80 + 96 == 816
    assert roofline.decide_bytes(128, 80) == 104_448  # a full 128-lane dispatch
    assert roofline.decide_bytes(1024, 80) == 835_584  # a full 1,024-lane dispatch
    assert roofline.decide_bytes(2, 80) == 1632  # two items in a 128-lane dispatch: padding is free
    assert roofline.decide_least_seconds(1024, 80, "TPU v5 lite") == pytest.approx(
        835_584 / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_decide_roofline_reader_cannot_pass_100_on_the_synthetic_trace():
    trace = trace_reduce.reduce_planes(trace_reduce.read_planes(
        os.path.join(ROOT, "benchmarks", "testdata", "synthetic.xplane.pb")))
    c = ctx(trace=trace, device={"device_kind": "TPU v5 lite"}, items_answered=1000,
            before={"gubernator_engine_flush_waves_sum": 5.0},
            after={"gubernator_engine_flush_waves_sum": 15.0})
    m = repo_manifest()
    path = manifest.reader_path(ROOT, manifest.bench_dir(m), "decide_roofline")
    # one span for both: its 1,000 items need 816,000 B = 0.996 us at 819 GB/s,
    # its 10 dispatches took 10 x 475 us (the trace: 4 decides in 1,900 us)
    assert readers.read(path, c) == pytest.approx(
        100 * (816_000 / 819e9) / (10 * 475e-6))
    assert readers.read(path, ctx(trace=trace, items_answered=1000)) is None


# ---- trace reduction ---------------------------------------------------------------------


@pytest.fixture(scope="module")
def synthetic():
    path = os.path.join(ROOT, "benchmarks", "testdata", "synthetic.xplane.pb")
    return trace_reduce.reduce_planes(trace_reduce.read_planes(path))


def test_trace_window_is_what_the_device_planes_span(synthetic):
    assert synthetic["window_s"] == pytest.approx(5300e-6)
    assert len(synthetic["devices"]) == 2


def test_trace_busy_and_idle_share(synthetic):
    d0, d1 = synthetic["devices"]
    assert d0["busy_s"] == pytest.approx(1400e-6) and d1["busy_s"] == pytest.approx(1000e-6)
    assert synthetic["busy_s"] == pytest.approx(1200e-6)
    assert synthetic["idle_share_pct"] == pytest.approx(100 * (1 - 1200 / 5300))


def test_trace_program_and_op_sums(synthetic):
    d0 = synthetic["devices"][0]
    assert d0["programs"]["jit_decide_fused"] == [3, pytest.approx(900e-6)]
    assert d0["programs"]["jit_census"] == [1, pytest.approx(500e-6)]
    ops = dict(d0["ops"])
    assert ops["%fusion.2 X64Combine"] == pytest.approx(600e-6)
    assert ops["%copy.1"] == pytest.approx(300e-6)
    c = ctx(trace=synthetic)
    events, secs = c.programs("decide")
    assert events == 2 and secs == pytest.approx(950e-6)  # per chip
    assert c.programs("no_such_program") is None


def test_trace_gaps_and_breakdown(synthetic):
    gaps = dict(synthetic["devices"][0]["gaps"])
    assert gaps["jit_decide_fused -> jit_decide_fused"] == pytest.approx(1700e-6)
    assert gaps["jit_decide_fused -> jit_census"] == pytest.approx(700e-6)
    assert gaps["jit_census -> jit_decide_fused"] == pytest.approx(1500e-6)
    g1 = dict(synthetic["devices"][1]["gaps"])
    assert g1["window opens -> jit_decide_fused"] == pytest.approx(1000e-6)
    assert g1["jit_decide_fused -> window closes"] == pytest.approx(3300e-6)
    b = synthetic["breakdown"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "%copy.1"  # 300 us on chip 0, 1000 us on chip 1
    assert b["device_ops"][0][1] == pytest.approx(650e-6)


def test_union_of_overlapping_intervals():
    assert trace_reduce.union_seconds([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == 4
    assert trace_reduce.union_seconds([]) == 0
    assert trace_reduce.program_name("jit_decide_fused(123)") == "jit_decide_fused"
    assert trace_reduce.op_label('%a.1 = f32[2] custom-call(), custom_call_target="X"') == "%a.1 X"


def test_synthetic_trace_is_what_its_script_writes():
    script = os.path.join(ROOT, "benchmarks", "testdata", "make_trace.py")
    src = open(script, encoding="utf-8").read()
    env: dict = {"__name__": "not_main", "__file__": script}
    exec(compile(src, script, "exec"), env)
    with open(os.path.join(ROOT, "benchmarks", "testdata", "synthetic.xplane.pb"), "rb") as f:
        assert f.read() == env["space"]


def test_run_py_refuses_a_checkout_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths`: non-zero, no result line."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "batching-10k.herd",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60)
    assert r.returncode != 0
    last = r.stdout.strip().splitlines()[-1]
    assert "BENCH FAILURE" in last
    with pytest.raises(ValueError):
        json.loads(last)
