"""GLOBAL limits that live and are hit again, on the ICI mapping: a herd
on resident keys, some of them used up, counted against the plain
reference and against the tick's own counters.

Four faked devices (a v5e host's mesh; tests/conftest.py forces eight).
Every key is made on every replica first (one hit, then the copies
meet), as the benchmark's `global-hot-4` preloads them. Then a seeded
scrambled-Zipf herd of two-item GLOBAL calls goes through the served
columnar path (`check_columns`) from several callers at once, so calls
share flushes, while a thread runs the sync tick (capped ticks and, every
eighth, a full one). After the copies have met again, every key is asked
four times at consecutive places of one call, which the round-robin
hands to the four replicas. What has to hold, exactly:

- a key the herd did not use up shows `limit - accepted` on all four
  replicas, which is what the plain reference holds after the same
  requests in one order; a used-up key is refused on all four and then
  shows what the reference shows, 0 left;
- the hits the replicas together took beyond what was left of the
  used-up keys are the growth of `gubernator_global_over_admitted_hits`;
- the accepted hits that landed on a replica other than the key's owner
  are the growth of `gubernator_global_merged_hits` (where each lane
  landed is read from the flush's own assembly);
- `gubernator_replica_decisions{device}` sums to the GLOBAL lanes sent,
  and every replica answered.
Counts only: no time is compared with anything."""

import threading

import jax
import numpy as np
import pytest

from benchmarks import traffic as bench_traffic
from benchmarks.reference.oracle import (
    GLOBAL, LEAKY_BUCKET, OVER_LIMIT, TOKEN_BUCKET, UNDER_LIMIT, Reference, Request)
from gubernator_tpu import wire
from gubernator_tpu.metrics import Metrics, wire_engine_telemetry
from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig
from gubernator_tpu.service import pb

NOW = 1_753_700_000_000
N_DEV = 4
KEYS, LIMIT, DURATION = 240, 24, 3_600_000
CALLERS, CALLS_EACH = 6, 150  # two items a call: 1,800 hits
ZIPF = {"distribution": "zipf", "s": 0.99, "scrambled": True}

pytestmark = pytest.mark.skipif(
    not wire.available(), reason="native wirepath unavailable")


@pytest.fixture(scope="module")
def engine():
    eng = IciEngine(
        IciEngineConfig(devices=jax.devices()[:N_DEV], num_groups=256,
                        num_slots=1 << 14, batch_size=64, batch_wait_s=0.001,
                        sync_wait_s=3600.0,  # the test's own thread ticks
                        max_sync_groups=512, full_tick_every=8),
        now_fn=lambda: NOW,
    )
    yield eng
    eng.close()


def columns(reqs):
    msg = pb.pb.GetRateLimitsReq()
    for r in reqs:
        msg.requests.append(pb.pb.RateLimitReq(
            name=r.name, unique_key=r.unique_key, hits=r.hits, limit=r.limit,
            duration=r.duration, algorithm=r.algorithm, behavior=r.behavior))
    return wire.parse_requests(msg.SerializeToString())


def send(eng, reqs):
    out = eng.check_columns(columns(reqs), now=NOW)
    assert out is not None
    return [tuple(int(col[i]) for col in out) for i in range(len(reqs))]


def counters(eng) -> dict:
    """The three series as `/metrics` has them."""
    m = Metrics()
    wire_engine_telemetry(m, eng)
    got = {}
    for line in m.render().decode().splitlines():
        if line.startswith(("gubernator_global_merged_hits ",
                            "gubernator_global_over_admitted_hits ",
                            "gubernator_replica_decisions{")):
            name, _, value = line.rpartition(" ")
            got[name] = int(float(value))
    return got


def grown(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def settle(eng) -> None:
    """Ticks until no group is left over, and twice more."""
    for _ in range(200):
        eng.sync_now()
        if eng.sync_backlog == 0:
            break
    assert eng.sync_backlog == 0
    eng.sync_now()
    eng.sync_now()


class Landed:
    """Where each GLOBAL lane of a flush landed, read from the flush's own
    assembly: accepted hits on a replica that does not own the key."""

    def __init__(self, eng):
        self.eng, self.lock, self.accepted_off_owner = eng, threading.Lock(), 0
        self.seen = threading.local()
        self.assemble, self.flush = (eng._assemble_replica_split,
                                     eng._check_columns_replica_split)
        eng._assemble_replica_split = self._assemble
        eng._check_columns_replica_split = self._flush

    def _assemble(self, *a, **kw):
        asm = self.assemble(*a, **kw)
        self.seen.asm = asm
        return asm

    def _flush(self, *a, **kw):
        out = self.flush(*a, **kw)
        r_asm = self.seen.asm[4]  # every item is GLOBAL: the replica waves
        waves, at = r_asm[0], list(zip(*r_asm[3]))  # each item's (wave, lane)
        home = np.array([waves[w].home[lane] for w, lane in at])
        groups_per = self.eng.num_rgroups // N_DEV
        owner = np.array(
            [waves[w].batch.group[lane] for w, lane in at]) // groups_per
        took = np.asarray(out[0]) == UNDER_LIMIT
        with self.lock:
            self.accepted_off_owner += int(np.sum(took & (home != owner)))
        return out

    def close(self):
        self.eng._assemble_replica_split = self.assemble
        self.eng._check_columns_replica_split = self.flush


@pytest.mark.deadline(240)
@pytest.mark.parametrize("seed", [43, 2147483691])
@pytest.mark.parametrize("algorithm", [TOKEN_BUCKET, LEAKY_BUCKET],
                         ids=["token", "leaky"])
def test_a_herd_on_resident_global_keys_is_counted_once_on_every_replica(
        engine, algorithm, seed):
    eng = engine

    def request(key_id, hits):
        return Request(name=f"hot{algorithm}s{seed}", unique_key=f"k{key_id:04d}",
                       hits=hits, limit=LIMIT, duration=DURATION,
                       algorithm=algorithm, behavior=GLOBAL, created_at=NOW)

    ref = Reference()

    def both(reqs):
        """To the engine and, in the same order, to the plain reference."""
        return send(eng, reqs), [r.as_tuple()[:4]
                                 for r in ref.get_rate_limits(reqs, NOW)]

    # every limit exists on every replica before the herd, one hit spent
    for off in range(0, KEYS, 60):
        got, want = both([request(k, 1) for k in range(off, off + 60)])
        assert got == want
    settle(eng)
    assert eng.overflow_keys == 0
    start = LIMIT - 1
    before = counters(eng)

    # the herd: several callers at once, the tick running beside them
    flat = bench_traffic.draw_keys(ZIPF, KEYS, CALLERS * CALLS_EACH * 2,
                                   bench_traffic.rng_for(seed, 1))
    calls = flat.reshape(CALLERS, CALLS_EACH, 2)
    answers = [[] for _ in range(CALLERS)]
    failures = []
    landed = Landed(eng)
    stop = threading.Event()

    def caller(c):
        try:
            for ids in calls[c]:
                answers[c].append(send(eng, [request(k, 1) for k in ids]))
        except Exception as e:  # surfaced below, in the test's thread
            failures.append(e)

    def ticker():
        while not stop.wait(0.01):
            eng.sync_now()

    threads = [threading.Thread(target=caller, args=(c,)) for c in range(CALLERS)]
    tick = threading.Thread(target=ticker)
    tick.start()
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        stop.set()
        tick.join()
        landed.close()
    assert not failures, failures
    settle(eng)
    herd = grown(counters(eng), before)

    sent = np.bincount(flat, minlength=KEYS)
    status = np.array([a[0] for c in range(CALLERS) for call in answers[c] for a in call])
    took = status == UNDER_LIMIT
    assert set(status.tolist()) <= {UNDER_LIMIT, OVER_LIMIT}
    accepted = np.bincount(flat[took], minlength=KEYS)
    used_up = sent >= start
    assert used_up.sum() >= 10 and (~used_up & (sent > 0)).sum() >= 100
    # a key that was not sent what was left is never refused
    assert (accepted == sent)[~used_up].all()
    # a used-up key took everything that was left, and what it took beyond
    # is what the owner's bucket could no longer take, hit for hit
    assert (accepted >= start)[used_up].all()
    over = int((accepted - start)[used_up].sum())
    assert herd["gubernator_global_over_admitted_hits"] == over
    assert herd["gubernator_global_merged_hits"] == landed.accepted_off_owner
    assert 0 < landed.accepted_off_owner < took.sum()
    by_device = [herd[f'gubernator_replica_decisions{{device="{d}"}}']
                 for d in range(N_DEV)]
    assert sum(by_device) == len(flat) and min(by_device) > 0

    # the reference takes the herd in one order: same totals, no lag
    for ids in calls.reshape(-1, 2):
        ref.get_rate_limits([request(k, 1) for k in ids], NOW)
    # a used-up key is refused by every replica (and by the reference)
    for ids in np.array_split(np.nonzero(used_up)[0], 3):
        refused, want = both([request(k, 1) for k in ids for _ in range(N_DEV)])
        assert refused == want and all(a[0] == OVER_LIMIT for a in refused)
    settle(eng)
    after_refusals = grown(counters(eng), before)
    # a refusal takes nothing, on its replica or at the owner
    assert after_refusals["gubernator_global_merged_hits"] == landed.accepted_off_owner
    assert after_refusals["gubernator_global_over_admitted_hits"] == over

    # every key on every replica: four consecutive places of one call
    before_probes = counters(eng)
    for off in range(0, KEYS, 15):  # 60 lanes a call, under the wave's 64
        ids = range(off, off + 15)
        got, want = both([request(k, 0) for k in ids for _ in range(N_DEV)])
        assert got == want, f"keys {off}.."
        for k, a in zip(np.repeat(list(ids), N_DEV), got):
            left = 0 if used_up[k] else LIMIT - 1 - accepted[k]
            assert a[2] == left, (k, a)
    probes = grown(counters(eng), before_probes)
    assert [probes[f'gubernator_replica_decisions{{device="{d}"}}']
            for d in range(N_DEV)] == [KEYS] * N_DEV


# ---- the tick's two counts, one key at a time ----------------------------------


@pytest.mark.parametrize("cap", [None, 16], ids=["full", "capped"])
@pytest.mark.parametrize("algorithm", [TOKEN_BUCKET, LEAKY_BUCKET],
                         ids=["token", "leaky"])
def test_the_tick_counts_what_it_merged_and_what_the_owner_could_not_take(
        algorithm, cap):
    """A key of 1,000 that its owner holds: two other replicas take 700
    each from copies that still show all of it, the owner 100. The tick
    applies 1,400 to an owner's bucket with 900 left: 500 over. A hit a
    replica refuses is not queued, and the next tick counts nothing."""
    from gubernator_tpu.api.keys import group_of, key_hash128
    from gubernator_tpu.api.types import Behavior, RateLimitReq
    from gubernator_tpu.ops.encode import encode_batch
    from gubernator_tpu.ops.layout import batch_entry
    from gubernator_tpu.parallel import ici
    from gubernator_tpu.parallel import mesh as pmesh

    mesh = pmesh.make_mesh(jax.devices()[:N_DEV])
    num_slots, ways = 64 * N_DEV, 4
    num_groups = num_slots // ways
    state = ici.create_ici_state(mesh, num_slots, ways)
    decide = batch_entry(ici.make_replica_decide(mesh, num_slots, ways))
    sync = ici.make_sync_step(mesh, num_slots, ways, max_sync_groups=cap)

    def hit(state, home, hits, now):
        req = RateLimitReq(name="m", unique_key="account:one", hits=hits,
                           limit=1000, duration=DURATION, algorithm=algorithm,
                           behavior=Behavior.GLOBAL)
        b = encode_batch([req], now, num_groups, 4)
        state, out = decide(state, b, np.full((4,), home, np.int64), now)
        return state, (int(out.status[0]), int(out.remaining[0]))

    def tick(state, now):
        for _ in range(8):  # a capped tick may leave groups for the next
            state, diag = sync(state, now)
            d = np.asarray(diag)
            assert d.shape == (N_DEV, 7)
            counts = (int(d[:, 5].sum()), int(d[:, 6].sum()))
            if int(d[:, 2].max()) == 0:
                return state, counts
        raise AssertionError("the backlog did not drain")

    group = group_of(key_hash128("m_account:one")[1], num_groups)
    owner = group // (num_groups // N_DEV)
    h1, h2 = (owner + 1) % N_DEV, (owner + 2) % N_DEV

    state, got = hit(state, owner, 0, NOW)  # the owner holds the bucket
    assert got == (UNDER_LIMIT, 1000)
    state, counts = tick(state, NOW + 1)
    assert counts == (0, 0)
    state, got = hit(state, h1, 700, NOW + 2)
    assert got == (UNDER_LIMIT, 300)
    state, got = hit(state, h2, 700, NOW + 3)
    assert got == (UNDER_LIMIT, 300)  # its own copy saw only its 700
    state, got = hit(state, owner, 100, NOW + 4)
    assert got == (UNDER_LIMIT, 900)
    state, counts = tick(state, NOW + 5)
    assert counts == (1400, 500)
    for home in range(N_DEV):  # drained to 0 on every replica, never below
        state, got = hit(state, home, 0, NOW + 6)
        assert got[1] == 0
    # a refusal takes nothing where it is given and queues nothing
    state, got = hit(state, h1, 1, NOW + 7)
    assert got == (OVER_LIMIT, 0)
    state, counts = tick(state, NOW + 8)
    assert counts == (0, 0)
