"""The Store path against the capacity-free reference, at a size where
the table cannot hold the keys: a 64-group x 8-way engine (512 slots)
with a MemoryStore, 4,000 keys, seeded scrambled-Zipf calls of 2, 100 and
1,000 items. With a Store an evicted key is read back on its next
request, so (a) every answer equals the reference's, which has no
capacity (gubernator_tpu/models/oracle.py); (b) at the end the Store
holds the reference's last state of every key touched; (c) the Store
path's counters (gubernator_store_*, gubernator_engine_wave_programs)
agree with what the Store saw and with each other.

Every call is served columnar: a 1,000-item call holds its hot key more
than `max_waves` times and is one flush of that many waves, each through
the Store's per-wave sequence.

The small twin of the benchmark's `store-1m.calls100` (PERF.md §4).
"""

import dataclasses

import numpy as np
import pytest

from gubernator_tpu import wire
from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.models.oracle import OracleEngine
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig
from gubernator_tpu.service import pb
from gubernator_tpu.store import MemoryStore, attach_store

NOW = 1_753_700_000_000
KEYS = 4_000
SIZES = (2,) * 28 + (100,) * 12 + (1_000,) * 3

pytestmark = pytest.mark.skipif(
    not wire.available(), reason="native wirepath unavailable"
)


class CountingStore(MemoryStore):
    """MemoryStore that counts what it is handed."""

    def __init__(self):
        super().__init__()
        self.changed = self.removed = self.get_hits = 0

    def on_change(self, items):
        self.changed += len(items)
        super().on_change(items)

    def remove(self, key):
        self.removed += 1
        super().remove(key)

    def get(self, req):
        snap = super().get(req)
        self.get_hits += snap is not None
        return snap


def counter(c) -> dict:
    """A _BareCounter's exposition as {label values or "": value}."""
    out = {}
    for line in c.render_lines():
        if not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            out[name.partition("{")[2].rstrip("}")] = float(value)
    return out


def store_counts(em) -> dict:
    gets = counter(em.store_gets)
    programs = counter(em.wave_programs)
    return {
        "hit": gets['result="hit"'], "miss": gets['result="miss"'],
        "injected": counter(em.store_injected_rows)[""],
        "changed": counter(em.store_on_change_items)[""],
        "removed": counter(em.store_removes)[""],
        **{p: programs[f'program="{p}"']
           for p in ("probe", "inject", "decide", "gather_rows")},
    }


def make_calls(seed: int):
    """[(requests of one call)]: scrambled Zipf(0.99) over KEYS, even
    keys token and odd keys leaky, one item in twenty a RESET_REMAINING."""
    rng = np.random.default_rng(seed)
    w = np.arange(1, KEYS + 1, dtype=np.float64) ** -0.99
    cdf = np.cumsum(w) / w.sum()
    scramble = rng.permutation(KEYS)
    sizes = np.array(SIZES)
    rng.shuffle(sizes)
    calls = []
    for size in sizes.tolist():
        ranks = np.minimum(np.searchsorted(cdf, rng.random(size)), KEYS - 1)
        reset = rng.random(size) < 0.05
        calls.append([
            RateLimitReq(
                name="ev", unique_key=f"k{k:05d}", hits=1, limit=20,
                duration=3_600_000,
                algorithm=(Algorithm.LEAKY_BUCKET if k % 2
                           else Algorithm.TOKEN_BUCKET),
                behavior=int(Behavior.RESET_REMAINING) if r else 0,
            )
            for k, r in zip(scramble[ranks].tolist(), reset.tolist())
        ])
    return calls


def to_proto_bytes(reqs):
    msg = pb.pb.GetRateLimitsReq()
    for r in reqs:
        msg.requests.append(pb.req_to_pb(r))
    return msg.SerializeToString()


@pytest.fixture(scope="module", params=[11, 12])
def run(request):
    """One seeded run: every answer beside the reference's, the per-call
    counter deltas, and the engine's final counters and Store."""
    clock = {"now": NOW}
    eng = DeviceEngine(
        EngineConfig(num_groups=64, ways=8, batch_size=64, batch_wait_s=0.001),
        now_fn=lambda: clock["now"],
    )
    store = CountingStore()
    attach_store(eng, store)
    oracle = OracleEngine()  # no capacity, no Store: the reference
    out = {"store": store, "oracle": oracle, "answers": [], "columnar": [],
           "touched": set()}
    try:
        for reqs in make_calls(request.param):
            clock["now"] += 10
            now = clock["now"]
            before = store_counts(eng.metrics)
            cols = wire.parse_requests(to_proto_bytes(reqs))
            # columnar whatever its size: a 1,000-item call holds its
            # hot key over max_waves times and runs the Store's per-wave
            # sequence for every one of its waves, in one flush
            got = list(zip(*(
                a.tolist() for a in eng.check_columns(cols, now=now))))
            want = [oracle.decide(dataclasses.replace(r), now) for r in reqs]
            want = [(int(w.status), w.limit, w.remaining, w.reset_time)
                    for w in want]
            after = store_counts(eng.metrics)
            out["answers"].append((len(reqs), got, want))
            out["columnar"].append((
                len(reqs), len({r.hash_key() for r in reqs}),
                {k: after[k] - before[k] for k in after}))
            out["touched"].update(r.hash_key() for r in reqs)
        em = eng.metrics
        out["counts"] = store_counts(em)
        out["waves"] = em.waves
        out["over_max_waves"] = int(
            em.flushes_over_max_waves.labels().get())
        out["evictions"] = em.unexpired_evictions
    finally:
        eng.close()
    return out


def test_answers_equal_the_capacity_free_reference(run):
    """(a) eviction or not: 4,000 keys through 512 slots."""
    assert run["evictions"] > 100 and run["counts"]["hit"] > 100
    for n, (size, got, want) in enumerate(run["answers"]):
        assert got == want, f"call {n} of {size} items"
    # every call was served columnar; the 1,000-item ones as flushes of
    # more than max_waves waves
    assert sorted(size for size, _, _ in run["columnar"]) == sorted(SIZES)
    assert run["over_max_waves"] == SIZES.count(1_000)


def test_store_holds_the_references_last_states(run):
    """(b) for every key touched: remaining, stamp, expire_at, status;
    a token key last freed by RESET_REMAINING is absent."""
    store, oracle = run["store"], run["oracle"]
    assert set(store.data) == set(oracle.cache) <= run["touched"]
    for key, item in oracle.cache.items():
        snap, v = store.data[key], item.value
        if item.algorithm == Algorithm.TOKEN_BUCKET:
            want = (v.remaining, v.created_at, item.expire_at, int(v.status))
            got = (snap.remaining, snap.stamp, snap.expire_at, snap.status)
        else:
            want = (v.remaining_s, v.updated_at, item.expire_at)
            got = (snap.remaining, snap.stamp, snap.expire_at)
        assert got == want, key
        assert (snap.algorithm, snap.limit, snap.duration) == (
            item.algorithm, v.limit, v.duration), key
    freed = run["touched"] - set(oracle.cache)
    assert freed, "no key ended freed: the run exercises no remove"


def test_store_counters_add_up(run):
    """(c) the counters agree with the Store's own view and each other."""
    c, store = run["counts"], run["store"]
    # what was handed to the Store: one snapshot or one remove a key a
    # flush, the key's last operation winning
    assert c["changed"] == store.changed and c["removed"] == store.removed
    assert c["hit"] == store.get_hits
    assert c["hit"] + c["miss"] == store.get_calls
    # a columnar call is one flush: every distinct key of it is handed
    # over once, so where no key repeats (most two-item calls) that is
    # the items answered
    for size, distinct, d in run["columnar"]:
        assert d["changed"] + d["removed"] == distinct, size
        # a run that ran stacked is one of each; one whose stacked probe
        # found a lane to read through is probed again wave by wave
        assert d["probe"] >= d["decide"] == d["gather_rows"] >= 1
        # every Store hit is injected before its wave's decide, and
        # nothing else counts as a row read through
        assert d["hit"] == d["injected"], size
    assert 0 < c["injected"] == c["hit"]
    # one probe, one decide and one row gather a wave, or one of each a
    # run where every key of a call was resident (the few calls here
    # that read nothing through: 4,000 keys through 512 slots); an
    # inject only where a wave had a row to seat
    assert c["probe"] >= c["decide"] == c["gather_rows"]
    assert 0.9 * run["waves"] < c["decide"] <= run["waves"]
    assert 0 < c["inject"] <= run["waves"]


# ---------------------------------------------------------------------------
# What a wave moves across the host-device boundary under the engine
# lock (ISSUE 37): gubernator_engine_store_wave_crossings.


def crossings(em) -> dict:
    c = counter(em.store_wave_crossings)
    return {d: c[f'direction="{d}"'] for d in ("h2d", "d2h")}


def small_engine(ways=8, num_groups=64):
    clock = {"now": NOW}
    eng = DeviceEngine(
        EngineConfig(num_groups=num_groups, ways=ways, batch_size=64,
                     batch_wait_s=0.001),
        now_fn=lambda: clock["now"],
    )
    store = CountingStore()
    attach_store(eng, store)
    return eng, store


def req(key, **kw):
    kw.setdefault("hits", 1)
    return RateLimitReq(name="ev", unique_key=key, limit=20,
                        duration=3_600_000, **kw)


def test_a_wave_without_a_miss_uploads_nothing_and_reads_one(monkeypatch):
    """(d) a columnar call of four waves, no key with anything to read
    through: under the engine lock nothing goes to the device (the
    probe reads the operand _upload left there, the row gather the
    decide's own output), which jax's transfer guard enforces on the
    launches themselves, and one array a launch comes back: the probe's
    answer. The output vector and the packed rows are read after the
    release (ISSUE 42). The first call's keys were never seen, so it
    knows it reads through and runs wave by wave; the second call's
    are resident and its four waves run stacked: one probe, one decide,
    one gather, one read (ISSUE 45)."""
    import jax

    from gubernator_tpu.runtime.engine import MeshEngine

    eng, store = small_engine()
    real = MeshEngine._execute_waves

    def under_guard(self, *a, **kw):
        with jax.transfer_guard_host_to_device("disallow"):
            return real(self, *a, **kw)

    monkeypatch.setattr(MeshEngine, "_execute_waves", under_guard)
    try:
        for n, reqs in enumerate((
            [req("a"), req("b"), req("dup"), req("dup"), req("dup"),
             req("dup")],               # never seen: prefetched, all absent
            [req("dup"), req("dup"), req("a"), req("dup"), req("dup")],
        )):                             # resident: every probe finds them
            before = {**store_counts(eng.metrics), **crossings(eng.metrics)}
            waves0 = eng.metrics.waves
            got = eng.check_columns(
                wire.parse_requests(to_proto_bytes(reqs)), now=NOW + n
            )
            assert got is not None
            after = {**store_counts(eng.metrics), **crossings(eng.metrics)}
            d = {k: after[k] - before[k] for k in after}
            waves = eng.metrics.waves - waves0
            assert waves == 4
            launches = (waves, 1)[n]
            assert d["probe"] == d["decide"] == d["gather_rows"] == launches
            assert d["inject"] == 0
            assert d["h2d"] == 0
            assert d["d2h"] == launches
        assert store.data["ev_dup"].remaining == 20 - 8
        assert eng.metrics.cold_compiles == 0
    finally:
        eng.close()


def test_a_wave_with_an_inject_counts_its_struct_and_its_answer():
    """The one upload left under the lock is the inject's 13-field
    operand, and it reads two key columns more: a wave with a miss the
    Store answered makes 13 + 3 crossings under the lock."""
    eng, store = small_engine()
    try:
        first = eng.check_columns(
            wire.parse_requests(to_proto_bytes([req("a", hits=3)])), now=NOW
        )
        assert first[2].tolist() == [17]
        # forget the row behind the engine's back, keep the string: the
        # probe misses and Store.get under the lock answers
        with eng._lock:
            eng.table = eng.K.create(eng.cfg.num_groups, eng.cfg.ways)
        before = crossings(eng.metrics)
        got = eng.check_columns(
            wire.parse_requests(to_proto_bytes([req("a")])), now=NOW + 1
        )
        assert got[2].tolist() == [16]  # continued from the Store's row
        after = crossings(eng.metrics)
        assert after["h2d"] - before["h2d"] == 13
        assert after["d2h"] - before["d2h"] == 1 + 2
    finally:
        eng.close()


def same_group_pair(num_groups):
    from gubernator_tpu.api.keys import group_of, key_hash128

    seen = {}
    for i in range(10_000):
        k = f"g{i}"
        g = group_of(key_hash128("ev_" + k)[1], num_groups)
        if g in seen:
            return seen[g], k
        seen[g] = k
    raise AssertionError("no two keys share a group")


@pytest.mark.parametrize("path", ["columnar", "object"])
@pytest.mark.parametrize("reset", [False, True], ids=["displaced", "freed"])
def test_a_key_displaced_between_its_own_waves(path, reset):
    """(e) one way a group, two keys of one group, one flush [A, B, A]:
    B's wave displaces A, and A's second wave must continue from the row
    A's first wave left, which only the flush's own gathered rows hold
    (the Store still has A's state from before the flush: five hits).
    Where A's first item is a RESET_REMAINING its row was freed, and the
    second starts a fresh bucket: the stale Store row is not read."""
    ka, kb = same_group_pair(16)
    eng, store = small_engine(ways=1, num_groups=16)
    try:
        eng.check_batch([req(ka, hits=5)])
        assert store.data[f"ev_{ka}"].remaining == 15
        first = req(ka, behavior=int(Behavior.RESET_REMAINING)) if reset \
            else req(ka)
        reqs = [first, req(kb), req(ka)]
        programs0 = store_counts(eng.metrics)
        read0 = crossings(eng.metrics)["d2h"]
        if path == "columnar":
            got = eng.check_columns(
                wire.parse_requests(to_proto_bytes(reqs)), now=NOW + 5
            )
            remaining = got[2].tolist()
        else:
            remaining = [r.remaining for r in eng.check_batch(reqs)]
        # RESET answers a full bucket and frees the row; then 20 - 1
        assert remaining == ([20, 19, 19] if reset else [14, 19, 13])
        d = store_counts(eng.metrics)
        # under the lock: three probes' answers, the first wave's rows
        # (still on the device when A's second wave asks for them), and
        # the two key columns of the inject where there is one
        assert crossings(eng.metrics)["d2h"] - read0 == 3 + 1 + (
            0 if reset else 2)
        assert d["decide"] - programs0["decide"] == 3  # a wave an item
        # never stacked (ISSUE 45 d): B was never seen, the flush knows
        # it reads through and every wave reads its own rows
        assert eng.metrics.recorder.last()["sequence"] == "per_wave"
        # A's second wave re-seats its own earlier row (displaced), or
        # finds nothing to seat (freed): the Store is not asked again
        assert d["inject"] - programs0["inject"] == (0 if reset else 1)
        # (only B, never seen, is looked up, and is not there)
        assert d["hit"] == programs0["hit"]
        assert store.data[f"ev_{ka}"].remaining == (19 if reset else 13)
        assert store.data[f"ev_{kb}"].remaining == 19
    finally:
        eng.close()
