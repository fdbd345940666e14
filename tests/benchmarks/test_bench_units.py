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
    consistency,
    daemon,
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


def test_hits_a_calls_items_differ_in_are_shared_out_exactly_whatever_the_seed():
    conf = {"keyspace": {"keys": 500, "limit": 100, "duration_ms": 5000,
                         "algorithm": "even_token_odd_leaky"}}
    traf = {"loop": "closed", "callers": 4, "pool_calls": 25, "items_per_call": 10,
            "hits": {"1": 0.80, "2": 0.10, "5": 0.08, "20": 0.02},
            "keys": {"distribution": "uniform"}}
    seen = []
    for seed in (3, 2**31 + 77):
        ks = traffic.Keyspace.from_config(conf, seed)
        p = traffic.build_plan(traf, ks, seed, 5.0)
        flat = np.concatenate(p.hits)
        vals, counts = np.unique(flat, return_counts=True)
        assert dict(zip(vals.tolist(), counts.tolist())) == {1: 800, 2: 100, 5: 80, 20: 20}
        assert [len(h) for h in p.hits] == [len(k) for k in p.keys]
        # the encoded call carries them, item for item
        sent = wire.GetReq.FromString(p.blobs[7]).requests
        assert [r.hits for r in sent] == p.hits[7].tolist()
        seen.append(flat)
    assert not np.array_equal(seen[0], seen[1])  # the same multiset, another order
    # a plain number is every item's, and a stream of its own moves nothing else
    one = traffic.build_plan(dict(traf, hits=3), ks, seed, 5.0)
    assert set(np.concatenate(one.hits).tolist()) == {3}
    assert [k.tolist() for k in one.keys] == [k.tolist() for k in p.keys]
    with pytest.raises(ValueError):
        traffic.build_plan(dict(traf, hits={"0": 0.5, "1": 0.5}), ks, seed, 5.0)


def test_a_flag_that_is_part_of_a_limits_definition_rides_on_every_request_of_its_key():
    conf = {"keyspace": {"keys": 4000, "limit": 100, "duration_ms": 5000,
                         "algorithm": "even_token_odd_leaky", "behavior": [],
                         "behavior_of_keys": [
                             {"one_in": 4, "behavior": ["DRAIN_OVER_LIMIT"]}]}}
    traf = {"loop": "closed", "callers": 4, "pool_calls": 25, "items_per_call": 10,
            "keys": {"distribution": "zipf", "s": 0.99, "scrambled": True},
            "behavior_shares": [{"share": 0.9, "behavior": []},
                                {"share": 0.1, "behavior": ["RESET_REMAINING"]}]}
    drain, reset = wire.BEHAVIOR["DRAIN_OVER_LIMIT"], wire.BEHAVIOR["RESET_REMAINING"]
    a, b = (traffic.Keyspace.from_config(conf, seed) for seed in (3, 2**31 + 77))
    assert np.array_equal(a.flags, b.flags)  # the rule is of the key, not of the seed
    flagged = a.flags == drain
    assert set(a.flags.tolist()) == {0, drain} and 900 < flagged.sum() < 1100
    ids = np.arange(a.n)
    assert 400 < (flagged & a.is_token(ids)).sum() < 600  # token and leaky alike
    k = int(np.nonzero(flagged)[0][0])
    assert a.request(k, 1).behavior == drain and a.request(k, 0).behavior == drain
    assert a.request(k, 1, behavior=0).behavior == 0  # what a caller states stands
    # an item's flags are its key's and its own event's
    p = traffic.build_plan(traf, a, 3, 5.0)
    keys, behs = np.concatenate(p.keys), np.concatenate(p.behaviors)
    assert np.array_equal(behs & drain, a.flags[keys])
    assert np.sum((behs & reset) != 0) == 100
    sent = wire.GetReq.FromString(p.blobs[7]).requests
    assert [r.behavior for r in sent] == p.behaviors[7].tolist()
    # a file without the rule: every key carries the keyspace's behaviour and no more
    del conf["keyspace"]["behavior_of_keys"]
    plain = traffic.Keyspace.from_config(conf, 3)
    assert plain.key_flags == () and plain.request(k, 1).behavior == 0
    assert not np.any(np.concatenate(traffic.build_plan(traf, plain, 3, 5.0).behaviors) & drain)


# sha-256 (first 16 hex digits) over the encoded calls of each cell's plan as
# the generator made them before it learnt per-item hits (the parent of PR 47),
# seed 2147483999, the keyspace cut to 20,000 keys, the file's `rehearsal`
# overrides, 3 s: a file whose `hits` is a plain number makes the same plan
PLANS_BEFORE = {
    "batching-10k.herd": (20000, "750cce508370daed"),
    "zipf-1m.saturate": (12, "2893a6a227dff4b1"),
    "batching-10k.steady": (192, "2f491c28e785fbf2"),
    "global-4.herd": (20000, "3e421de95cc43e5d"),
    "zipf-1m.calls100": (32, "b67f49bdb8d5ed9c"),
    "sharded-4.calls100": (32, "b67f49bdb8d5ed9c"),
    "store-1m.calls100": (32, "b67f49bdb8d5ed9c"),
    "zipf-1m.steady": (158, "bc56a58377687676"),
    "store-4.calls100": (32, "b67f49bdb8d5ed9c"),
    "batching-10k.burst": (150, "e061e07270d34089"),
    "global-hot-4.herd-zipf": (1600, "e1cc288e944262ae"),
    # since PR 48, the calls100 family's fifth: the same file over the same keys
    "loader-1m.calls100": (32, "b67f49bdb8d5ed9c"),
}


@pytest.mark.parametrize("cell", sorted(PLANS_BEFORE))
def test_the_plan_of_every_cell_that_was_there_is_byte_for_byte_what_it_was(cell):
    import hashlib

    m = manifest.load(ROOT)
    w = next(x for x in m["workloads"] if x["name"] == cell)
    entry = next(c for c in m["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, entry["file"]), encoding="utf-8") as f:
        conf = json.load(f)
    with open(manifest.traffic_path(ROOT, manifest.bench_dir(m), w["traffic"]),
              encoding="utf-8") as f:
        traf = json.load(f)
    conf["keyspace"]["keys"] = min(conf["keyspace"]["keys"], 20000)
    traf.update(traf.get("rehearsal", {}))
    seed = 2147483999
    p = traffic.build_plan(traf, traffic.Keyspace.from_config(conf, seed), seed, 3.0)
    digest = hashlib.sha256()
    for blob in p.blobs:
        digest.update(blob)
    assert (len(p.blobs), digest.hexdigest()[:16]) == PLANS_BEFORE[cell]
    assert set(np.concatenate(p.hits).tolist()) == {int(traf.get("hits", 1))}


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


def items_of(rows, behavior=0, ks=KS):
    """rows: (key, status, remaining, reset_time), then optionally the hits
    the item asked for (1) and the flags it carried (`behavior`)"""
    rows = [tuple(r) + (1, behavior)[len(r) - 4:] for r in rows]
    a = np.asarray(rows, dtype=np.int64).reshape(-1, 6)
    return check.Items(key=a[:, 0], status=a[:, 1], limit=np.full(len(a), ks.limit),
                       remaining=a[:, 2], reset_time=a[:, 3],
                       valid=np.ones(len(a), bool), behavior=a[:, 5], hits=a[:, 4])


def window(rows, carried=None, uncertain=(), ks=KS):
    unc = np.zeros(ks.n, bool)
    unc[list(uncertain)] = True
    wc = check.WindowCheck(ks, carried or check.Carried.empty(ks.n), unc)
    v = check.Verdict()
    wc.check_window(items_of(rows, ks=ks), v)
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


# ---- hits above one, DRAIN_OVER_LIMIT, RESET_REMAINING (PR 47) -----------------------
# rows: (key, status, remaining, reset_time, hits, flags)

DRAIN, RESET = wire.BEHAVIOR["DRAIN_OVER_LIMIT"], wire.BEHAVIOR["RESET_REMAINING"]
MIXED = traffic.Keyspace(name="t", n=1000, limit=10, duration_ms=5000,
                         algorithm="even_token_odd_leaky", behavior=0, salt=7)


def carried_at(key, remaining, reset_time=R, ks=KS):
    c = check.Carried.empty(ks.n)
    c.remaining[key], c.reset_time[key] = remaining, reset_time
    return c


def test_accepted_items_of_mixed_hits_tile_the_generation_in_any_order():
    rows = [(5, 0, 0, R, 2), (5, 0, 8, R, 2), (5, 0, 2, R, 1), (5, 0, 3, R, 5)]
    wc, v = window(rows)
    assert v.correct, v.lines()
    assert wc.counted["items_hits_over_1"] == 3 and wc.counted["generations"] == 1
    assert window(rows[::-1])[1].correct


@pytest.mark.parametrize("rows", [
    [(5, 0, 8, R, 2), (5, 0, 2, R, 3), (5, 0, 1, R, 1)],  # the hit of 3 applied twice
    [(5, 0, 8, R, 2), (5, 0, 8, R, 2)],                   # a hit of 2 not counted
    [(5, 0, 7, R, 2)],                                    # began under the start
    [(5, 0, 7, R, 3), (5, 1, 7, R, 8), (5, 0, 2, R, 1)],  # the refusal consumed
], ids=["applied-twice", "not-counted", "began-low", "refusal-consumed"])
def test_a_gap_or_an_overlap_in_the_tiling_is_not_exact(rows):
    _, v = window(rows)
    assert row(v, "window.token_generations_not_exact") == 1 and not v.correct


def test_a_refusal_shows_a_remainder_under_its_hits_that_the_generation_passed():
    # 7 left: 20 and 8 are refused whole and take nothing; 20 of a full new bucket too
    rows = [(5, 0, 7, R, 3), (5, 1, 7, R, 20), (5, 1, 7, R, 8), (5, 0, 3, R, 4),
            (6, 1, 10, R + 1, 20)]
    wc, v = window(rows)
    assert v.correct, v.lines()
    assert wc.counted["refused"] == 3 and wc.counted["refused_with_remainder"] == 3


@pytest.mark.parametrize("rows,name", [
    ([(5, 0, 7, R, 3), (5, 1, 7, R, 5)], "window.over_limit_with_remaining"),  # 7 >= 5
    ([(5, 0, 7, R, 3), (5, 1, 4, R, 5)], "window.over_limit_before_used_up"),  # never at 4
    ([(5, 1, 0, R, 5)], "window.over_limit_before_used_up"),                   # never at 0
    # 0 shown after nine accepted hits of one: a hit counted twice emptied it
    ([(5, 0, r, R) for r in (9, 7, 6, 5, 4, 3, 2, 1, 0)] + [(5, 1, 0, R)],
     "window.over_limit_before_used_up"),
], ids=["enough-was-left", "a-value-never-passed", "zero-never-passed",
        "emptied-by-a-hit-counted-twice"])
def test_a_refusal_with_enough_left_or_a_value_never_passed_is_caught(rows, name):
    _, v = window(rows)
    assert row(v, name) == 1 and not v.correct, v.lines()


def test_a_refusal_that_drains_leaves_nothing_and_the_probe_sees_nothing():
    rows = [(5, 0, 3, R, 7), (5, 1, 0, R, 5, DRAIN), (5, 1, 0, R, 1), (5, 1, 0, R, 2, DRAIN),
            (6, 0, 3, R, 7), (6, 1, 0, R, 5, DRAIN),
            (8, 0, 0, R, 10), (8, 1, 0, R, 5, DRAIN)]  # used up, then a drain that met 0
    wc, v = window(rows)
    assert v.correct, v.lines()
    assert wc.counted["generations_drained"] == 2 and wc.counted["items_drain"] == 4
    # the status sticks where a request met the empty bucket: not for the
    # refusal that emptied it
    wc.check_probes(items_of([(5, 1, 0, R), (6, 0, 0, R), (8, 1, 0, R)]), v)
    assert v.correct, v.lines()
    v2 = check.Verdict()
    wc.check_probes(items_of([(5, 1, 3, R), (6, 1, 0, R)]), v2)  # not emptied; stuck early
    assert row(v2, "probe.mismatches") == 2


@pytest.mark.parametrize("rows", [
    [(5, 0, 3, R, 7), (5, 1, 3, R, 5, DRAIN)],  # refused and left 3: the flag was dropped
    [(5, 0, 6, R, 4), (5, 1, 0, R, 5, DRAIN)],  # refused 5 though the bucket never fell under 6
], ids=["left-remaining", "never-fell-that-low"])
def test_a_drain_that_left_something_is_caught(rows):
    _, v = window(rows)
    assert row(v, "window.drain_left_remaining") == 1 and not v.correct, v.lines()


def test_a_reset_removes_the_bucket_or_is_the_first_of_a_generation():
    later = R + 100
    rows = [(5, 0, 5, R, 1), (5, 0, 10, 0, 3, RESET), (5, 0, 8, later, 2),
            (6, 0, 7, later, 3, RESET)]  # key 6 had no bucket
    wc, v = window(rows, carried_at(5, 6))
    assert v.correct, v.lines()
    assert wc.removed[5] == 1 and wc.counted["reset_removed_bucket"] == 1
    assert wc.counted["generations_after_reset"] == 1
    assert wc.evicted == set()  # the removal paid for the generation made early


@pytest.mark.parametrize("answer,name", [
    ((5, 0, 4, R, 2, RESET), "window.reset_not_fresh"),   # applied as a plain hit
    ((5, 1, 0, R, 2, RESET), "window.reset_not_fresh"),   # refused
    ((5, 0, 10, R, 2, RESET), "window.reset_not_fresh"),  # full, but the bucket stayed
    ((5, 0, 8, R, 2, RESET), "window.token_generations_not_exact"),  # "first" of a live one
], ids=["plain-hit", "refused", "bucket-stayed", "first-of-a-live-generation"])
def test_a_reset_answered_otherwise_is_caught(answer, name):
    _, v = window([answer], carried_at(5, 6))
    assert row(v, name) == 1 and not v.correct, v.lines()


def test_a_leaky_key_is_held_to_its_range_its_refusals_and_its_resets():
    rows = [(7, 0, 8, R, 2, RESET), (7, 0, 3, R + 9, 5), (7, 1, 1, R + 9, 2),
            (7, 1, 0, R + 9, 5, DRAIN)]
    assert window(rows, ks=MIXED)[1].correct
    _, v = window([(7, 0, 5, R, 2, RESET)], ks=MIXED)  # not refilled to burst less 2
    assert row(v, "window.reset_not_fresh") == 1
    _, v = window([(7, 1, 3, R, 2)], ks=MIXED)  # refused 2 with 3 left
    assert row(v, "window.over_limit_with_remaining") == 1
    _, v = window([(7, 1, 1, R, 2, DRAIN)], ks=MIXED)  # drained, and 1 is left
    assert row(v, "window.drain_left_remaining") == 1


@pytest.mark.parametrize("rows,evicted", [
    ([(5, 0, 9, R), (5, 0, 10, 0, 1, RESET), (5, 0, 9, R + 100)], set()),
    ([(5, 0, 9, R), (5, 0, 9, R + 100)], {5}),
    ([(5, 0, 9, R), (5, 0, 10, 0, 1, RESET), (5, 0, 9, R + 100), (5, 0, 9, R + 200)], {5}),
    ([(5, 0, 9, R), (5, 0, 10, 0, 1, RESET), (5, 0, 9, R + 100),
      (5, 0, 10, 0, 2, RESET), (5, 0, 8, R + 200, 2)], set()),
], ids=["one-removal-one-generation", "no-removal", "one-removal-two-generations",
        "two-and-two"])
def test_a_removal_pays_for_one_generation_made_while_the_old_one_lived(rows, evicted):
    wc, v = window(rows)
    assert v.correct and wc.evicted == evicted, v.lines()


def test_two_generations_of_one_millisecond_split_into_two_tilings():
    at = R + 100
    rows = [(5, 0, 9, at, 1), (5, 0, 10, 0, 1, RESET), (5, 0, 7, at, 3), (5, 0, 6, at, 1)]
    wc, v = window(rows)
    assert v.correct and wc.evicted == set(), v.lines()
    assert wc.split == {(5, at): [6, 9]}
    assert wc.counted["generations_under_one_reset_time"] == 1
    assert wc.counted["generations"] == 2
    # no order says which of the two the probe met
    for left in (6, 9):
        v = check.Verdict()
        wc.check_probes(items_of([(5, 0, left, at)]), v)
        assert v.correct, v.lines()
    wc.check_probes(items_of([(5, 0, 8, at)]), v)
    assert not v.correct
    # without the removal's answer the same items are a hit that was not counted
    _, v = window([r for r in rows if r[3]])
    assert row(v, "window.token_generations_not_exact") == 1
    # three stretches that end at the top and one removal: one bucket too many
    wc, v = window(rows + [(5, 0, 8, at, 2)])
    assert v.correct and wc.evicted == {5}


def test_chains_split_from_the_top_down():
    assert check._chains([9, 7, 6], [1, 3, 1], 10) == [6, 9]
    assert check._chains([8, 8, 3, 6], [2, 2, 5, 2], 10) == [3, 6]
    assert check._chains([8, 3], [2, 4], 10) is None  # a gap
    assert check._chains([8, 6, 6], [2, 2, 2], 10) is None  # two from one place


def test_a_failed_calls_keys_are_held_to_no_overlap_with_hits_above_one():
    rows = [(5, 0, 8, R, 2), (5, 0, 2, R, 3)]  # a gap: the lost call's hits
    assert window(rows, uncertain=[5])[1].correct
    assert not window(rows)[1].correct
    _, v = window([(5, 0, 8, R, 2), (5, 0, 7, R, 3)], uncertain=[5])  # 8..10 taken twice
    assert row(v, "window.token_generations_not_exact") == 1
    # what a lost call may have moved is not held against a refusal or a drain
    _, v = window([(5, 1, 4, R, 5), (5, 1, 0, R, 5, DRAIN)], uncertain=[5])
    assert v.correct, v.lines()


def test_probes_equal_the_start_less_the_sum_of_the_accepted_hits():
    wc, v = window([(5, 0, 8, R, 2), (5, 0, 3, R, 5)])
    wc.check_probes(items_of([(5, 0, 3, R)]), v)
    assert v.correct, v.lines()
    v2 = check.Verdict()
    wc.check_probes(items_of([(5, 0, 8, R)]), v2)  # the start less the count of them
    assert row(v2, "probe.mismatches") == 1


@pytest.mark.parametrize("removal,evicted", [(True, set()), (False, {5})])
def test_a_probe_that_makes_a_bucket_after_a_removal_saw_no_eviction(removal, evicted):
    rows = [(5, 0, 9, R)] + ([(5, 0, 10, 0, 1, RESET)] if removal else [])
    wc, v = window(rows)
    wc.check_probes(items_of([(5, 0, 10, R + 300)]), v)  # a bucket of its own making
    assert v.correct and wc.evicted == evicted, v.lines()


def mixed_window(fault=None, calls=400, seed=2147483747):
    """The plain reference put in the server's place under a mixed plan at a
    small size (`calls100`'s calls; hits a share table, DRAIN_OVER_LIMIT a
    part of one limit in four, RESET_REMAINING on 2 % of the items), callers
    in turn, one time a call; every 20th call broken as control.py breaks it."""
    with open(os.path.join(ROOT, "benchmarks/traffic/calls100.json"),
              encoding="utf-8") as f:
        traf = dict(json.load(f), callers=8, pool_calls=12,
                    hits={"1": 0.80, "2": 0.10, "5": 0.08, "20": 0.02},
                    behavior_shares=[{"share": 0.98, "behavior": []},
                                     {"share": 0.02, "behavior": ["RESET_REMAINING"]}])
    ks = traffic.Keyspace(name="t", n=4000, limit=100, duration_ms=3_600_000,
                          algorithm="even_token_odd_leaky", behavior=0, salt=9,
                          key_flags=((4, DRAIN),))
    plan = traffic.build_plan(traf, ks, seed, 5.0)
    server, t_pin = ref.Reference(), 1_700_000_000_000
    for k in range(ks.n):  # the preload: one hit on every key
        server.get_rate_limits([ks.request(k, 1, created_at=t_pin)], t_pin)
    pools = [np.nonzero(plan.caller_of == c)[0] for c in range(plan.callers)]
    rows, now, last = [], t_pin + 60_000, None
    for n in range(calls):
        i = int(pools[n % 8][(n // 8) % len(pools[n % 8])])
        now += 6
        broken = fault and (n + 1) % 20 == 0
        flags = [int(b) & ~(DRAIN | RESET) if broken and fault == "strip_flags" else int(b)
                 for b in plan.behaviors[i]]
        names = {}
        if broken and fault == "forget":
            names = {"name": f"forgotten{n}"}

        def ask():
            reqs = [ks.request(k, int(h), created_at=now, behavior=b)
                    for k, h, b in zip(plan.keys[i], plan.hits[i], flags)]
            for r in reqs:
                r.name = names.get("name", r.name)
            return [a.as_tuple()[:4] for a in server.get_rate_limits(reqs, now)]

        got = ask()
        if broken and fault == "double_apply":
            got = ask()
        if fault == "stale_answer":
            got, last = (last if broken and last else got), got
        rows += [(k,) + a + (h, b) for k, a, h, b in
                 zip(plan.keys[i], got, plan.hits[i], plan.behaviors[i])]
    a = np.asarray(rows, dtype=np.int64)
    it = check.Items(key=a[:, 0], status=a[:, 1], limit=a[:, 2], remaining=a[:, 3],
                     reset_time=a[:, 4], valid=np.ones(len(a), bool), behavior=a[:, 6],
                     hits=a[:, 5])
    carried = check.Carried.empty(ks.n)
    tok = ks.is_token(np.arange(ks.n))
    carried.remaining[tok], carried.reset_time[tok] = ks.limit - 1, t_pin + ks.duration_ms
    wc = check.WindowCheck(ks, carried, np.zeros(ks.n, bool))
    v = check.Verdict()
    wc.check_window(it, v)
    ids = np.unique(a[:, 0])
    now += 1000
    p = np.asarray([server.get_rate_limits([ks.request(k, 0, created_at=now)], now)[0]
                    .as_tuple()[:4] for k in ids.tolist()], dtype=np.int64)
    wc.check_probes(check.Items(
        key=ids, status=p[:, 0], limit=p[:, 1], remaining=p[:, 2], reset_time=p[:, 3],
        valid=np.ones(len(ids), bool), behavior=np.zeros(len(ids), np.int64),
        hits=np.zeros(len(ids), np.int64)), v)
    wc.check_evictions(len(ids), 1 << 16, 8, v)  # a table in which nothing is evicted
    return wc, v


def test_the_reference_in_the_servers_place_reads_every_row_nought():
    wc, v = mixed_window()
    assert v.correct and wc.evicted == set(), v.lines()
    assert all(x == 0 for _, x, _ in v.rows), v.lines()
    c = wc.counted
    # the rows had work: hits above one, refusals with a remainder, drains,
    # removals and the generations made after them
    assert c["items"] == 40_000 and c["items_hits_over_1"] > 0.15 * c["items"]
    assert c["refused_with_remainder"] > 20 and c["generations_drained"] > 3
    assert c["reset_removed_bucket"] > 200 and c["generations_after_reset"] > 150


@pytest.mark.parametrize("fault,rows", [
    ("double_apply", {"window.token_generations_not_exact"}),
    ("stale_answer", {"window.token_generations_not_exact"}),
    ("forget", {"evicted_keys"}),
    ("strip_flags", {"window.reset_not_fresh", "window.drain_left_remaining"}),
])
def test_the_reference_broken_as_the_controls_break_the_server_is_not_correct(fault, rows):
    # a stripped DRAIN_OVER_LIMIT shows only where it met a remainder: a longer window
    _, v = mixed_window(fault, calls=1200 if fault == "strip_flags" else 400)
    failed = {n for n, x, lim in v.rows if x > lim}
    assert not v.correct and failed & rows, v.lines()
    if fault == "strip_flags":  # each of the two new rows sees it
        assert rows <= failed, v.lines()


def test_sequential_follows_hits_and_flags_and_a_removed_bucket_carries_nothing():
    served = ref.Reference()
    now = 5_000

    def send(reqs):
        return [r.as_tuple() for r in served.get_rate_limits(copy.deepcopy(reqs), now)]

    seq = check.Sequential(send)
    steps = [(4, 0), (20, 0), (20, DRAIN), (1, 0), (3, RESET), (2, 0)]
    got = [seq.call("c", [3], [KS.request(3, h, created_at=now, behavior=b)], now)[0][:3]
           for h, b in steps]
    assert got == [(0, 10, 6), (1, 10, 6), (1, 10, 0), (1, 10, 0), (0, 10, 10), (0, 10, 8)]
    assert seq.mismatches == 0 and seq.token_state(3, KS) == (8, now + 5000, False)
    seq.call("c", [3], [KS.request(3, 1, created_at=now, behavior=RESET)], now)
    assert seq.token_state(3, KS) is None  # run.py then carries no bucket for the key


def test_the_control_that_strips_the_flags_is_one_of_the_kinds():
    from benchmarks import control

    assert control.KINDS == ("double_apply", "stale_answer", "forget", "strip_flags")
    assert control.STRIPPED == DRAIN | RESET == 40


# ---- a guarantee that is eventual -----------------------------------------------------

GLOBAL = wire.BEHAVIOR["GLOBAL"]
BORN = (R - KS.duration_ms + 1000, R - KS.duration_ms + 4000)  # the window, in ms
JOIN = 100  # copies_join_within_ms


def eventual_window(rows, sent, carried=None, uncertain=(), born=BORN):
    """rows as `items_of`, all GLOBAL; sent: {key: hits the window sent}."""
    unc = np.zeros(KS.n, bool)
    unc[list(uncertain)] = True
    if carried is None:  # every key preloaded: 9 left in the generation R
        carried = check.Carried.empty(KS.n)
        carried.remaining[:], carried.reset_time[:] = 9, R
    wc = check.WindowCheck(KS, carried, unc)
    v = check.Verdict()
    n = np.zeros(KS.n, np.int64)
    for k, c in sent.items():
        n[k] = c
    it = items_of(rows, behavior=GLOBAL)
    wc.check_window(it, v)  # the plain rows see no GLOBAL item
    wc.check_window_eventual(it, n, born, JOIN, v)
    return wc, v


def row(v, name):
    return dict((n, x) for n, x, _ in v.rows)[name]


def test_eventual_window_accepts_what_any_copy_may_answer():
    # three hits on key 5 from three copies that have not met: each saw its own only;
    # key 6: one copy took both
    _, v = eventual_window(
        [(5, 0, 8, R), (5, 0, 8, R), (5, 0, 8, R), (6, 0, 8, R), (6, 0, 7, R)],
        {5: 3, 6: 2})
    assert v.correct, v.lines()
    assert [n for n, _, _ in v.rows][-3:] == [
        "window.global_generation_unknown", "window.global_remaining_out_of_range",
        "window.global_over_limit_before_used_up"]
    assert row(v, "window.token_generations_not_exact") == 0  # no plain item


@pytest.mark.parametrize("rows,sent,name", [
    ([(5, 0, 7, R)], {5: 1}, "window.global_remaining_out_of_range"),  # counted twice
    ([(5, 0, 9, R)], {5: 1}, "window.global_remaining_out_of_range"),  # not counted
    ([(5, 1, 0, R)], {5: 3}, "window.global_over_limit_before_used_up"),
    ([(5, 0, 8, R + 77_000)], {5: 1}, "window.global_generation_unknown"),
    ([(5, 0, 8, R - 1)], {5: 1}, "window.global_generation_unknown"),
], ids=["below-range", "above-range", "over-early", "born-after", "born-before"])
def test_eventual_window_catches(rows, sent, name):
    _, v = eventual_window(rows, sent)
    assert not v.correct
    assert row(v, name) == 1, v.lines()


def test_eventual_window_over_limit_where_the_sent_hits_use_the_bucket_up():
    c = check.Carried.empty(KS.n)
    c.remaining[5], c.reset_time[5] = 2, R
    _, v = eventual_window([(5, 0, 1, R), (5, 0, 1, R), (5, 1, 0, R)], {5: 3}, c)
    assert v.correct, v.lines()


def test_eventual_window_counts_a_generation_made_while_the_old_one_lived():
    born_now = BORN[0] + KS.duration_ms + 500  # a bucket made 500 ms into the window
    wc, v = eventual_window([(5, 0, 8, R), (5, 0, 9, born_now)], {5: 2})
    assert v.correct and wc.evicted == {5}


def test_eventual_window_counts_a_failed_calls_hits_in_the_range():
    # the failed call's hit may have been applied: 7 is inside [9 - 3, 8]
    _, v = eventual_window([(5, 0, 7, R), (5, 0, 8, R)], {5: 3}, uncertain=[5])
    assert v.correct, v.lines()


def repeated(rows, n=8):
    return [items_of(rows, behavior=GLOBAL) for _ in range(n)]


def test_repeated_probes_hold_every_copy_to_the_total():
    wc, v = eventual_window([(5, 0, 8, R), (5, 0, 8, R), (6, 0, 8, R)], {5: 2, 6: 1})
    wc.check_probes_eventual(repeated([(5, 0, 7, R), (6, 0, 8, R), (7, 0, 9, R)]), v)
    assert v.correct, v.lines()
    assert [n for n, _, _ in v.rows][-3:] == [
        "probe.failed", "probe.global_mismatches", "probe.global_answers_disagree"]


def test_repeated_probes_catch_one_copy_that_lags():
    wc, _ = eventual_window([(5, 0, 8, R), (5, 0, 8, R)], {5: 2})
    reps = repeated([(5, 0, 7, R)])
    reps[3] = items_of([(5, 0, 8, R)], behavior=GLOBAL)  # one copy missed a hit
    v = check.Verdict()
    wc.check_probes_eventual(reps, v)
    assert row(v, "probe.global_mismatches") == 1
    assert row(v, "probe.global_answers_disagree") == 1
    assert "1 of 8 answers" in " ".join(v.lines())


def test_repeated_probes_catch_a_hit_counted_twice_everywhere():
    wc, _ = eventual_window([(5, 0, 8, R)], {5: 1})
    v = check.Verdict()
    wc.check_probes_eventual(repeated([(5, 0, 7, R)]), v)
    assert row(v, "probe.global_mismatches") == 1
    assert row(v, "probe.global_answers_disagree") == 0


def test_repeated_probes_skip_uncertain_used_up_and_evicted_keys():
    c = check.Carried.empty(KS.n)
    c.remaining[:], c.reset_time[:] = 9, R
    c.remaining[8] = 2
    made = BORN[1] + KS.duration_ms + 50  # a bucket a probe itself made, 950 ms early
    wc, _ = eventual_window(
        [(5, 0, 8, R), (8, 0, 1, R), (8, 0, 1, R), (8, 1, 0, R)], {5: 2, 8: 3}, c,
        uncertain=[5])
    v = check.Verdict()
    wc.check_probes_eventual(
        repeated([(5, 0, 8, R), (8, 1, 0, R), (9, 0, 10, made)]), v)
    assert v.correct, v.lines()
    assert wc.evicted == {9}  # held by the eviction allowance, not by equality
    wc.check_evictions(3, 4096, 4, v)
    assert v.correct


def test_repeated_probes_count_failed_answers():
    wc, _ = eventual_window([(6, 0, 8, R)], {6: 1})
    reps = repeated([(5, 0, 9, R)], n=2)
    reps[1].valid[0] = False
    v = check.Verdict()
    wc.check_probes_eventual(reps, v)
    assert row(v, "probe.failed") == 1 and not v.correct


def scrapes_of(monkeypatch, rows):
    """`consistency.scrape` answers the rows in turn, then the last for ever."""
    it = iter(rows)
    state = {}

    def fake(addr):
        state["last"] = next(it, state.get("last"))
        return state["last"]

    monkeypatch.setattr(consistency, "scrape", fake)
    monkeypatch.setattr(consistency, "POLL_S", 0.0)


SPEC = {"quiesce_timeout_s": 0.3, "probe_repeats": 8, "copies_join_within_ms": 1000,
        "quiescent_when": {"gauge": "backlog", "equals": 0, "then_ticks": 3,
                           "tick_count": "ticks"}}


def test_quiescence_is_the_gauge_at_rest_for_then_ticks(monkeypatch):
    scrapes_of(monkeypatch, [
        {"backlog": 0.0, "ticks": 10.0}, {"backlog": 0.0, "ticks": 12.0},
        {"backlog": 5.0, "ticks": 13.0},  # work arrived: the count starts again
        {"backlog": 0.0, "ticks": 14.0}, {"backlog": 0.0, "ticks": 16.0},
        {"backlog": 0.0, "ticks": 17.0}, {"backlog": 7.0, "ticks": 99.0}])
    ev = consistency.Eventual(SPEC, "nowhere")
    ev.wait("in a test")
    assert ev.waits == 1 and ev.waited_s >= 0.0
    assert ev.probe_repeats == 8 and ev.join_ms == 1000  # the file's own


@pytest.mark.parametrize("rows", [
    [{"backlog": 3.0, "ticks": 1.0}],  # never drains
    [{"backlog": 0.0, "ticks": 1.0}],  # the tick has stopped
    [{"ticks": 1.0}],  # the program lacks the gauge
], ids=["backlog", "no-ticks", "no-gauge"])
def test_a_wait_that_passes_its_timeout_fails_the_run(monkeypatch, rows):
    scrapes_of(monkeypatch, rows)
    with pytest.raises(daemon.BenchFailure):
        consistency.Eventual(SPEC, "nowhere").wait("in a test")


# buckets that live 5 s: two copies may each make one before they have met


def fresh(rows, sent):
    """No key carried: every bucket is made in the window, three lives long."""
    return eventual_window(rows, sent, carried=check.Carried.empty(KS.n),
                           born=(BORN[0], BORN[0] + 3 * KS.duration_ms))


MADE = BORN[0] + KS.duration_ms + 700  # the reset time of a bucket made 700 ms in


def test_two_copies_that_each_made_the_bucket_are_one_bucket():
    wc, v = fresh([(5, 0, 9, MADE), (5, 0, 9, MADE + 40), (5, 0, 8, MADE + 40)], {5: 3})
    assert v.correct and not wc.evicted and wc.joined == 1
    # the copies kept one of the two, with all three hits counted
    wc.check_probes_eventual(repeated([(5, 0, 7, MADE)]), v)
    assert v.correct and wc.held_exact == 1, v.lines()


def test_copies_that_kept_different_ones_of_the_two_disagree():
    wc, _ = fresh([(5, 0, 9, MADE), (5, 0, 9, MADE + 40)], {5: 2})
    reps = repeated([(5, 0, 8, MADE)])
    reps[5] = items_of([(5, 0, 8, MADE + 40)], behavior=GLOBAL)
    v = check.Verdict()
    wc.check_probes_eventual(reps, v)
    assert row(v, "probe.global_mismatches") == 0
    assert row(v, "probe.global_answers_disagree") == 1


@pytest.mark.parametrize("apart,evicted", [(JOIN, set()), (JOIN + 1, {5})])
def test_a_second_bucket_made_later_than_copies_take_to_join_is_an_eviction(apart, evicted):
    wc, v = fresh([(5, 0, 9, MADE), (5, 0, 9, MADE + apart)], {5: 2})
    assert v.correct and wc.evicted == evicted


@pytest.mark.parametrize("before,evicted", [(-1, set()), (JOIN - 1, set()), (JOIN, {5})])
def test_a_bucket_may_be_gone_as_long_before_its_time_as_a_sync_takes(before, evicted):
    # the next bucket begins `before` ms before the first one's reset time
    wc, v = fresh([(5, 0, 9, MADE), (5, 0, 9, MADE - before + KS.duration_ms)], {5: 2})
    assert v.correct and wc.evicted == evicted and wc.joined == 0


def test_probes_that_made_their_own_buckets_differ_in_reset_time_alone():
    wc, _ = fresh([(5, 0, 9, MADE)], {5: 1})
    after = MADE + 300  # the bucket's time is up: each copy a probe meets makes one
    reps = [items_of([(5, 0, 10, after + KS.duration_ms + 7 * n), (6, 0, 10, after + n)],
                     behavior=GLOBAL) for n in range(8)]
    v = check.Verdict()
    wc.check_probes_eventual(reps, v)
    assert v.correct and not wc.evicted and wc.held_exact == 0, v.lines()
    reps[2].remaining[0] = 9  # a hit outlived its bucket on one copy
    v = check.Verdict()
    wc.check_probes_eventual(reps, v)
    assert row(v, "probe.global_mismatches") == 1
    assert row(v, "probe.global_answers_disagree") == 1


def test_a_probe_that_finds_a_live_bucket_gone_counts_an_eviction():
    wc, _ = fresh([(5, 0, 9, MADE)], {5: 1})
    v = check.Verdict()
    wc.check_probes_eventual(repeated([(5, 0, 10, MADE + 3000)]), v)  # made 2 s early
    assert v.correct and wc.evicted == {5}


class FakePlan:
    keys = [np.array(k) for k in ([1, 2], [3, 3], [4, 5], [2, 6], [7, 8], [9, 10])]
    behaviors = [np.array([GLOBAL, GLOBAL])] * 6
    hits = [np.array([1, 1])] * 6


def test_the_set_up_check_of_an_eventual_configuration_sends_each_call_twice():
    from benchmarks import run as bench_run

    sent = []

    class Seq:
        def call(self, what, ids, reqs, now):
            sent.append((tuple(ids.tolist()), now))

    made = bench_run.setup_check(Seq(), KS, FakePlan, 1000, 6, 60.0,
                                 settle=lambda: sent.append("settle"))
    # [3, 3] is passed over; [2, 6] meets key 2 again, and so does the second round
    assert made == 6
    assert [x if x == "settle" else x[0] for x in sent] == [
        (1, 2), (4, 5), "settle", (2, 6), "settle", (1, 2), (4, 5), "settle", (2, 6)]
    assert [x[1] for x in sent if x != "settle"] == [1010, 1020, 1030, 1040, 1050, 1060]
    # an exact configuration: the plan's calls as they come, no wait
    sent.clear()
    assert bench_run.setup_check(Seq(), KS, FakePlan, 1000, 3, 60.0) == 3
    assert [x[0] for x in sent] == [(1, 2), (3, 3), (4, 5)]


def test_recent_keys_are_those_of_the_windows_last_seconds():
    from benchmarks import run as bench_run

    res = {"call": np.array([0, 2, 3, 4]), "sent": np.array([1.0, 6.9, 7.0, 9.5])}
    got = bench_run.recent_keys(FakePlan, res, 10.0, 3.0)
    assert got.tolist() == [2, 6, 7, 8]
    assert bench_run.recent_keys(FakePlan, res, 10.0, 0.1).tolist() == []


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
    assert "columnar_call_share" in herd and "gen_late_p99_ms" not in herd
    # retired in PR 47: it read flushes over calls, about 100 / calls_per_flush
    assert "fast_path_share" not in {p["name"] for p in m["per_layer"]}
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


def test_sync_bytes_by_hand():
    # 100 groups x 4 ways x (80 B slot + 8 B pending) = 35,200 B merged: read and
    # written on the chip's own copy, three quarters of it received from the others
    assert roofline.sync_bytes(100, 4, 88, 4) == (70_400, 26_400)
    # ICI is the bound: 26,400 B at 200 GB/s = 132 ns > 70,400 B at 819 GB/s = 86 ns
    assert roofline.sync_least_seconds(100, 4, 88, 4, "TPU v5 lite") == pytest.approx(
        26_400 / 200e9)
    assert roofline.sync_bytes(100, 4, 88, 1) == (70_400, 0)  # one chip: nothing to receive


FOUR = {"devices": [{"programs": {"jit_sync_fn": [10, 0.020], "jit_decide_fn": [50, 0.010],
                                  "jit_census": [1, 0.5]}} for _ in range(4)]}
GLOBAL_TABLE = {"ways": 8, "tiers": {"replica": {"ways": 4, "groups": 262144}}}
V5E4 = {"device_kind": "TPU v5 lite", "device_count": 4}


def global_reader(name):
    m = repo_manifest()
    return manifest.reader_path(ROOT, manifest.bench_dir(m), name)


def test_the_tick_readers_against_a_synthetic_pair_of_scrapes():
    c = ctx(trace=FOUR, table=GLOBAL_TABLE, device=V5E4, phases={"quiesce_s": 9.5},
            before={"gubernator_ici_tick_duration_sum": 1.0,
                    "gubernator_ici_tick_duration_count": 100.0,
                    "gubernator_ici_tick_groups_sum": 1000.0,
                    "gubernator_ici_tick_groups_count": 100.0},
            after={"gubernator_ici_tick_duration_sum": 1.5,
                   "gubernator_ici_tick_duration_count": 200.0,
                   "gubernator_ici_tick_groups_sum": 401_000.0,
                   "gubernator_ici_tick_groups_count": 200.0})
    assert readers.read(global_reader("ici_tick_ms"), c) == pytest.approx(5.0)
    assert readers.read(global_reader("ici_tick_groups"), c) == pytest.approx(4000.0)
    assert readers.read(global_reader("ici_tick_device_us"), c) == pytest.approx(2000.0)
    assert readers.read(global_reader("quiesce_s"), c) == 9.5
    # 4,000 groups a tick: 1,056,000 B received at 200 GB/s = 5.28 us of a 2 ms tick
    got = readers.read(global_reader("ici_tick_roofline"), c)
    assert got == pytest.approx(100 * (4000 * 4 * 88 * 0.75 / 200e9) / 2e-3)
    assert 0 < got < 100
    # the exact configurations' runs have no replica tier and no tick: nothing to read
    assert readers.read(global_reader("ici_tick_roofline"), ctx(trace=FOUR)) is None
    assert readers.read(global_reader("quiesce_s"), ctx()) is None


def test_replica_decide_roofline_counts_each_chips_share_of_the_items():
    c = ctx(trace=FOUR, table=GLOBAL_TABLE, device=V5E4, items_answered=1000,
            before={"gubernator_engine_flush_waves_sum": 0.0},
            after={"gubernator_engine_flush_waves_sum": 500.0})
    # 250 lanes a chip x (4 ways x 80 + 80 + 96) B = 124,000 B; 500 dispatches x 200 us
    got = readers.read(global_reader("replica_decide_roofline"), c)
    assert got == pytest.approx(100 * (250 * 496 / 819e9) / (500 * 200e-6))
    assert 0 < got < 100
    one_tier = ctx(trace=FOUR, device=V5E4, items_answered=1000)
    assert readers.read(global_reader("replica_decide_roofline"), one_tier) is None


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
