"""A Store flush whose keys are all resident runs its waves as one
stacked sequence (ISSUE 45): one probe, one decide and one row gather a
run under the engine lock, one blocking read. Anything else keeps the
per-wave sequence, decided by what the engine observes. Counts and
equalities only: against models/oracle.py, and against the same engine
with no stacked Store shape warm, which runs every flush wave by wave.
"""

import dataclasses
import logging

import jax
import numpy as np
import pytest

from gubernator_tpu import wire
from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.models.oracle import OracleEngine
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig
from gubernator_tpu.service import pb
from gubernator_tpu.store import MemoryStore, attach_store

NOW = 1_753_700_000_000
PROGRAMS = ("probe", "inject", "decide", "gather_rows")

pytestmark = pytest.mark.skipif(
    not wire.available(), reason="native wirepath unavailable"
)


def counts(em) -> dict:
    """What the Store's sequence launched, read under the lock and how
    its flushes ran, since the engine started."""
    return {
        **{p: em.wave_programs.labels(p).get() for p in PROGRAMS},
        "read": em.store_wave_crossings.labels("d2h").get(),
        "uploaded": em.store_wave_crossings.labels("h2d").get(),
        "stacked": em.store_flushes.labels("stacked").get(),
        "per_wave": em.store_flushes.labels("per_wave").get(),
        "surprises": em.store_stacked_surprises.labels().get(),
        "skipped": em.store_rows_skipped.labels().get(),
        "waves": em.waves,
        "gets": sum(em.store_gets.labels(r).get() for r in ("hit", "miss")),
    }


def delta(em, before: dict) -> dict:
    after = counts(em)
    return {k: after[k] - before[k] for k in after}


def req(key, **kw):
    kw.setdefault("hits", 1)
    kw.setdefault("duration", 3_600_000)
    return RateLimitReq(name="st", unique_key=key, limit=100, **kw)


def columns(reqs):
    msg = pb.pb.GetRateLimitsReq()
    for r in reqs:
        msg.requests.append(pb.req_to_pb(r))
    return wire.parse_requests(msg.SerializeToString())


class Bench:
    """An engine with a MemoryStore beside the reference: every call's
    answers are held to the oracle's as it is made."""

    def __init__(self, stacked=True, store=None, **cfg):
        self.clock = NOW
        cfg = {"num_groups": 1 << 12, "batch_size": 128, **cfg}
        self.eng = DeviceEngine(
            EngineConfig(batch_wait_s=0.001, **cfg),
            now_fn=lambda: self.clock,
        )
        self.store = MemoryStore() if store is None else store
        attach_store(self.eng, self.store)
        if not stacked:
            # no stacked Store shape warm: every flush runs wave by wave
            self.eng._warm_store_stacks = ()
        self.oracle = OracleEngine()
        self.em = self.eng.metrics

    def call(self, reqs, path="columnar"):
        """One call at the next millisecond; (answers, counter deltas,
        the flush's flight-recorder record)."""
        self.clock += 1
        before = counts(self.em)
        if path == "columnar":
            out = self.eng.check_columns(columns(reqs), now=self.clock)
            got = list(zip(*(a.tolist() for a in out)))
        else:
            got = [(int(r.status), r.limit, r.remaining, r.reset_time)
                   for r in self.eng.check_batch(reqs)]
        want = [self.oracle.decide(dataclasses.replace(r), self.clock)
                for r in reqs]
        assert got == [
            (int(w.status), w.limit, w.remaining, w.reset_time) for w in want
        ]
        return got, delta(self.em, before), self.em.recorder.last()

    def close(self):
        self.eng.close()


@pytest.fixture
def pair():
    """The engine under test and its per-wave twin (no stacked Store
    shape warm), each with a Store of its own."""
    benches = [Bench(), Bench(stacked=False)]
    yield benches
    for b in benches:
        b.close()


def page(hot: int, others: int = 93):
    """A call of `hot` + `others` items: one key `hot` times among
    `others` distinct keys, the hot key's items spread through it."""
    reqs = [req(f"k{i}") for i in range(others)]
    for i in range(hot):
        reqs.insert((i * others) // hot, req("hot"))
    return reqs


def same_stores(a: MemoryStore, b: MemoryStore):
    assert set(a.data) == set(b.data)
    for key, snap in a.data.items():
        assert snap == b.data[key], key


# ---- (a), (b): the stacked sequence ---------------------------------------


def test_seven_waves_of_resident_keys_are_one_of_each(pair):
    """(a) one resident key 7 times in a 100-item call: 7 waves, one
    probe, one decide, one row gather, one read under the lock and
    nothing uploaded there; the answers are the oracle's (Bench.call)
    and the per-wave twin's, the Store's entries equal key for key."""
    stacked, per_wave = pair
    for b in pair:
        b.call(page(1))  # every key resident and persisted
    got, d, rec = stacked.call(page(7))
    assert d["waves"] == 7 and rec["waves"] == 7
    assert [d[p] for p in PROGRAMS] == [1, 0, 1, 1]
    assert (d["read"], d["uploaded"]) == (1, 0)
    assert (d["stacked"], d["per_wave"], d["gets"]) == (1, 0, 0)
    assert (rec["sequence"], rec["launches"]) == ("stacked", 1)

    twin, d2, rec2 = per_wave.call(page(7))
    assert twin == got
    assert [d2[p] for p in PROGRAMS] == [7, 0, 7, 7] and d2["read"] == 7
    assert (d2["stacked"], d2["per_wave"]) == (0, 1)
    assert rec2["sequence"] == "per_wave"
    same_stores(stacked.store, per_wave.store)
    assert stacked.store.data["st_hot"].remaining == 100 - 8
    for b in pair:
        assert counts(b.em)["surprises"] == counts(b.em)["skipped"] == 0
        assert b.em.cold_compiles == 0


def test_forty_of_one_key_are_the_launches_upload_cuts(pair):
    """(b) a hot key 40 times, over max_waves (32): two stacked runs as
    _upload cuts them (32 waves at depth 32, 8 at depth 8), a probe, a
    decide, a gather and a read each."""
    stacked, per_wave = pair
    for b in pair:
        b.call(page(1))
    got, d, rec = stacked.call(page(40))
    assert d["waves"] == 40
    assert [d[p] for p in PROGRAMS] == [2, 0, 2, 2] and d["read"] == 2
    assert (rec["sequence"], rec["launches"]) == ("stacked", 2)
    twin, d2, _ = per_wave.call(page(40))
    assert twin == got and d2["decide"] == 40
    same_stores(stacked.store, per_wave.store)
    assert stacked.store.data["st_hot"].remaining == 100 - 41
    assert counts(stacked.em)["surprises"] == 0
    assert stacked.em.cold_compiles == 0


@pytest.mark.parametrize("path", ["columnar", "object"])
def test_both_paths_share_the_sequence(path):
    """The object path (the pump) runs the same stacked sequence: the
    change lives in _upload and _execute_waves, which both call."""
    b = Bench()
    try:
        b.call([req("a"), req("b")], path)
        _, d, rec = b.call([req("a"), req("b"), req("a"), req("a")], path)
        assert d["waves"] == 3
        assert [d[p] for p in PROGRAMS] == [1, 0, 1, 1] and d["read"] == 1
        assert rec["sequence"] == "stacked" and rec["path"] == path
        assert b.store.data["st_a"].remaining == 100 - 4
    finally:
        b.close()


def test_leaky_and_token_lanes_and_a_drain_run_stacked(pair):
    """Every behaviour but RESET_REMAINING updates its row in place: a
    run with leaky lanes, a DRAIN_OVER_LIMIT refusal and hits of 0
    runs stacked and equals the oracle and the per-wave twin."""
    drain = int(Behavior.DRAIN_OVER_LIMIT)

    def reqs(n):
        out = []
        for i in range(n):
            out.append(req("tok", hits=30))
            out.append(req("leak", algorithm=Algorithm.LEAKY_BUCKET, hits=7))
            out.append(req("drain", hits=60, behavior=drain))
            out.append(req("peek", hits=0))
        return out

    stacked, per_wave = pair
    for b in pair:
        b.call(reqs(1))
    got, d, rec = stacked.call(reqs(5))
    assert rec["sequence"] == "stacked" and d["waves"] == 5
    assert any(g[0] == 1 for g in got)  # OVER_LIMIT answers among them
    twin, _, rec2 = per_wave.call(reqs(5))
    assert twin == got and rec2["sequence"] == "per_wave"
    same_stores(stacked.store, per_wave.store)
    assert counts(stacked.em)["surprises"] == 0


# ---- (c): what keeps the per-wave sequence --------------------------------


def run_fallback(b: Bench, reqs, **want):
    _, d, rec = b.call(reqs)
    assert rec["sequence"] == "per_wave"
    assert (d["stacked"], d["per_wave"]) == (0, 1)
    assert d["decide"] == d["gather_rows"] == d["waves"] > 1
    for k, v in want.items():
        assert d[k] == v, (k, d)
    assert counts(b.em)["surprises"] == counts(b.em)["skipped"] == 0
    assert b.em.cold_compiles == 0
    return d


def test_a_never_seen_key_keeps_the_flush_per_wave():
    """(c) the flush knows before its upload that it reads through: no
    stacked probe is spent on it (as many probes as waves; the Store
    is asked outside the lock and, having had nothing, under it)."""
    b = Bench()
    try:
        b.call([req("a"), req("a")])
        run_fallback(b, [req("a"), req("new"), req("a")], probe=2, gets=2)
    finally:
        b.close()


def test_a_prefetched_key_keeps_the_flush_per_wave():
    """(c) a key the Store holds and this process has never seen (a
    restart over the same Store): prefetched outside the lock, injected
    by its wave."""
    first = Bench()
    first.call([req("a", hits=5), req("b")])
    first.close()
    b = Bench(store=first.store)
    b.oracle = first.oracle
    b.clock = first.clock
    try:
        d = run_fallback(b, [req("a"), req("b"), req("a")], probe=2, inject=1)
        assert d["gets"] == 2
        assert b.store.data["st_a"].remaining == 100 - 7
        # resident now: the same call runs stacked
        _, d, rec = b.call([req("a"), req("b"), req("a")])
        assert rec["sequence"] == "stacked" and d["probe"] == 1
    finally:
        b.close()


def test_a_key_the_probe_finds_expired_hands_the_run_back_wave_by_wave():
    """(c) nothing tells the host that a bucket's time is up: the
    stacked probe finds the lane not live, the run's waves are handed
    back one by one on the device (no upload, no compile) and run the
    per-wave sequence: one probe more than waves."""
    b = Bench()
    try:
        b.call([req("long"), req("short", duration=50), req("long")])
        b.clock += 1_000
        # the run's probe, then a probe a wave; the Store's (expired)
        # entry of the key is read back and seated, as it always was
        run_fallback(
            b, [req("long"), req("short", duration=50), req("long")],
            probe=1 + 2, inject=1, gets=1,
        )
    finally:
        b.close()


def test_a_row_gone_behind_the_hosts_back_is_read_through_per_wave():
    """(c), (d) both keys' strings are known and their rows are gone
    (a key displaced in an earlier flush that came back before the
    hygiene dropped its string; here the table emptied and the string
    put back behind the engine's back): the stacked probe sends the
    run to the per-wave sequence, which reads each key back from the
    Store under the lock and, with one way a group, re-seats the key
    displaced between its own waves from the flush's own rows."""
    from gubernator_tpu.api.keys import key_hash128

    ka, kb = same_group_pair(16)
    b = Bench(num_groups=16, ways=1, batch_size=32)
    try:
        b.call([req(ka, hits=5)])
        b.call([req(kb, hits=2)])  # displaces A: its string is dropped
        b.call([req(ka)])          # and A, read back, displaces B
        with b.eng._lock:
            b.eng.table = b.eng.K.create(16, 1)
        with b.eng._keys_lock:
            b.eng._key_strings[key_hash128(f"st_{kb}")] = f"st_{kb}"
        d = run_fallback(b, [req(ka), req(kb), req(ka)], probe=1 + 3)
        assert d["inject"] == 3 and d["gets"] == 2
        assert b.store.data[f"st_{ka}"].remaining == 100 - 8
        assert b.store.data[f"st_{kb}"].remaining == 100 - 3
    finally:
        b.close()


def same_group_pair(num_groups):
    from gubernator_tpu.api.keys import group_of, key_hash128

    seen = {}
    for i in range(10_000):
        k = f"g{i}"
        g = group_of(key_hash128("st_" + k)[1], num_groups)
        if g in seen:
            return seen[g], k
        seen[g] = k
    raise AssertionError("no two keys share a group")


def test_a_reset_lane_keeps_the_flush_per_wave(pair):
    """(c) RESET_REMAINING is the one way a decide frees a row
    (ops/decide.py _token_paths `used=~m_reset`): gated on the
    behaviour column before the upload, so no stacked probe is spent."""
    reset = int(Behavior.RESET_REMAINING)
    reqs = [req("a"), req("b"), req("a", behavior=reset), req("a")]
    for b in pair:
        b.call([req("a", hits=9), req("b")])
        d = run_fallback(b, reqs, probe=3)
        assert b.store.data["st_a"].remaining == 99
    same_stores(pair[0].store, pair[1].store)


def test_a_pager_keeps_the_flush_per_wave():
    """(c) page promotion is per wave: a paged table warms no stacked
    Store shape and never stacks."""
    b = Bench(num_groups=1 << 8, page_groups=16, page_budget=8)
    try:
        assert b.eng._warm_store_stacks == ()
        b.call([req("a"), req("b")])
        run_fallback(b, [req("a"), req("b"), req("a")], probe=2)
    finally:
        b.close()


def test_one_wave_is_its_own_sequence():
    """A flush of one wave is a probe, a decide and a gather either
    way; it counts per_wave."""
    b = Bench()
    try:
        b.call([req("a")])
        _, d, rec = b.call([req("a"), req("b")])
        assert rec["sequence"] == "per_wave" and d["waves"] == 1
        assert [d[p] for p in PROGRAMS] == [1, 0, 1, 1]
    finally:
        b.close()


# ---- the guard and the exposition -----------------------------------------


def test_a_stacked_run_that_misses_is_counted_as_a_surprise(monkeypatch):
    """The guard is a live sensor: a probe that lies (every lane
    reported live over an emptied table) lets the run go stacked, its
    decide misses, and the read after the lock counts it."""
    b = Bench()
    try:
        b.call([req("a"), req("a")])
        with b.eng._lock:
            b.eng.table = b.eng.K.create(b.eng.cfg.num_groups, 8)
        K = b.eng.K
        monkeypatch.setattr(b.eng, "K", K._replace(
            probe_exists=lambda t, op, ways: jax.numpy.asarray(
                np.asarray(op)[..., 11, :] != 0  # OP_ALGO_ACTIVE: active
            )
        ))
        b.oracle = OracleEngine()  # the table forgot: so does the reference
        b.store.data.clear()
        _, d, rec = b.call([req("a"), req("a"), req("a")])
        assert rec["sequence"] == "stacked" and d["surprises"] == 1
    finally:
        b.close()


def test_the_two_series_are_exposed_at_zero_and_count():
    from gubernator_tpu.metrics import Metrics, wire_engine_telemetry

    b = Bench()
    try:
        m = Metrics()
        wire_engine_telemetry(m, b.eng)

        def exposed():
            return {
                ln.rpartition(" ")[0]: float(ln.rpartition(" ")[2])
                for ln in m.render().decode().splitlines()
                if ln.startswith("gubernator_engine_store_")
            }

        zero = exposed()
        assert zero['gubernator_engine_store_flushes{sequence="stacked"}'] == 0
        assert zero['gubernator_engine_store_flushes{sequence="per_wave"}'] == 0
        assert zero["gubernator_engine_store_stacked_surprises"] == 0
        b.call([req("a"), req("a")])
        b.call([req("a"), req("a")])
        got = exposed()
        assert got['gubernator_engine_store_flushes{sequence="stacked"}'] == 1
        assert got['gubernator_engine_store_flushes{sequence="per_wave"}'] == 1
        assert got["gubernator_engine_store_stacked_surprises"] == 0
    finally:
        b.close()


# ---- (g): the programs keep their names ------------------------------------


def test_stacked_shapes_compile_under_the_names_the_readers_match(caplog):
    """(g) the roofline readers find a program by `probe_exists`,
    `gather_rows` and `decide` in its name: the stacked shapes are new
    shapes of the same jitted functions, on one device and on a mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gubernator_tpu.ops.kernels import get_kernels
    from gubernator_tpu.ops.layout import NCOLS, WaveOperand
    from gubernator_tpu.parallel.mesh import make_mesh, make_mesh_kernels

    depth, lanes, groups, ways = 3, 8, 32, 8  # shapes nothing else compiles
    mesh = make_mesh(jax.devices()[:2])
    sets = {
        "fused": (get_kernels("fused"), None),
        "fn": (make_mesh_kernels(mesh, "fused", groups, ways),
               NamedSharding(mesh, P())),
    }
    jax.config.update("jax_log_compiles", True)
    try:
        for suffix, (K, sharding) in sets.items():
            caplog.clear()
            with caplog.at_level(logging.WARNING, logger="jax"):
                table = K.create(groups, ways)
                op = jax.device_put(
                    WaveOperand.zeros(lanes, depth).stamp(NOW).buf, sharding
                )
                found = K.probe_exists(table, op, ways)
                table, out = K.decide_packed(table, op, ways, True)
                rows = K.gather_rows(table, out, True)
            assert found.shape == (depth, lanes)
            assert rows.shape == (depth, NCOLS, lanes)
            compiled = [
                r.getMessage().split()[1] for r in caplog.records
                if r.getMessage().startswith("Compiling ")
            ]
            for name in ("probe_exists", "decide", "gather_rows"):
                assert f"jit({name}_{suffix})" in compiled, (name, compiled)
    finally:
        jax.config.update("jax_log_compiles", False)
