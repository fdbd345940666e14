"""A Store on the owner-sharded table (ISSUE 41): the four-chip daemon's
engine (IciEngine over four faked devices) with a MemoryStore that counts
what it is handed, against a reference that shares no code with it: the
oracle's answers, and a plain dict that holds, per key, the oracle's
bucket after the key's last acknowledged request (a token bucket's
RESET_REMAINING removes the entry, reference algorithms.go:78-90).

"The same operations on the same data give the same answers" means here:
the same answers to the client AND the same entries in the Store, from
whichever chip owns the key. Every case is a count, never a time. On the
parent's parallel/mesh.py (the slot column a sum of shard-local indices)
cases (i)-(iii) fail: three quarters of the changes never reach the
Store.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gubernator_tpu import wire
from gubernator_tpu.api.keys import key_hash128
from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.models.oracle import OracleEngine
from gubernator_tpu.ops import fused
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig
from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig
from gubernator_tpu.service import pb
from gubernator_tpu.store import MemoryStore, attach_store

NOW = 1_753_700_000_000
CHIPS = 4
RESET = int(Behavior.RESET_REMAINING)
GLOBAL = int(Behavior.GLOBAL)

pytestmark = pytest.mark.skipif(
    not wire.available(), reason="native wirepath unavailable"
)


class CountingStore(MemoryStore):
    """MemoryStore that counts what it is handed and keeps the keys it
    was asked for and the keys it answered."""

    def __init__(self):
        super().__init__()
        self.changed = self.removed = 0
        self.asked, self.answered = [], []

    def on_change(self, items):
        self.changed += len(items)
        super().on_change(items)

    def remove(self, key):
        self.removed += 1
        super().remove(key)

    def get(self, req):
        snap = super().get(req)
        self.asked.append(req.hash_key())
        if snap is not None:
            self.answered.append(req.hash_key())
        return snap


class ReferenceStore:
    """What a Store must hold: per key the oracle's (algorithm, status,
    limit, duration, remaining, stamp, expire_at, burst) after the key's
    last request; nothing for a token key last freed by RESET_REMAINING."""

    def __init__(self):
        self.oracle = OracleEngine()
        self.entries = {}

    def apply(self, r: RateLimitReq, now: int):
        want = self.oracle.decide(dataclasses.replace(r), now)
        key = r.hash_key()
        item = self.oracle.cache.get(key)
        if item is None:
            self.entries.pop(key, None)
        else:
            v = item.value
            token = item.algorithm == Algorithm.TOKEN_BUCKET
            self.entries[key] = (
                int(item.algorithm), int(v.status) if token else 0,
                v.limit, v.duration,
                v.remaining if token else v.remaining_s,
                v.created_at if token else v.updated_at,
                item.expire_at, 0 if token else v.burst,
            )
        return (int(want.status), want.limit, want.remaining, want.reset_time)


def entry_of(snap) -> tuple:
    token = snap.algorithm == Algorithm.TOKEN_BUCKET
    return (
        int(snap.algorithm), int(snap.status) if token else 0, snap.limit,
        snap.duration, snap.remaining, snap.stamp, snap.expire_at,
        0 if token else snap.burst,
    )


def counts(eng) -> dict:
    def one(c, label=""):
        for ln in c.render_lines():
            if not ln.startswith("#") and label in ln:
                return float(ln.rpartition(" ")[2])
        raise KeyError(label)

    em = eng.metrics
    return {
        "hit": one(em.store_gets, 'result="hit"'),
        "injected": one(em.store_injected_rows),
        "changed": one(em.store_on_change_items),
        "removed": one(em.store_removes),
        "skipped": one(em.store_rows_skipped),
        "stacked": em.store_flushes.labels("stacked").get(),
        "per_wave": em.store_flushes.labels("per_wave").get(),
        "surprises": em.store_stacked_surprises.labels().get(),
    }


def skipped(eng) -> float:
    return counts(eng)["skipped"]


def calls100(seed: int, n_calls: int, keys: int, items: int = 100,
             reset_share: float = 0.05, global_share: float = 0.0):
    """Seeded calls of the `calls100` shape: `items` a call, scrambled
    Zipf(0.99) over a small keyspace, so keys repeat inside a call and
    its waves stack; even keys token, odd keys leaky."""
    rng = np.random.default_rng(seed)
    w = np.arange(1, keys + 1, dtype=np.float64) ** -0.99
    cdf = np.cumsum(w) / w.sum()
    scramble = rng.permutation(keys)
    calls = []
    for _ in range(n_calls):
        ranks = np.minimum(np.searchsorted(cdf, rng.random(items)), keys - 1)
        reset = rng.random(items) < reset_share
        glob = rng.random(items) < global_share
        calls.append([
            RateLimitReq(
                name="st4", unique_key=f"{'g' if g else 'k'}{k:05d}",
                hits=1, limit=20, duration=3_600_000,
                algorithm=(Algorithm.TOKEN_BUCKET if g or k % 2 == 0
                           else Algorithm.LEAKY_BUCKET),
                behavior=GLOBAL if g else (RESET if r else 0),
            )
            for k, r, g in zip(scramble[ranks].tolist(), reset.tolist(),
                               glob.tolist())
        ])
    return calls


def to_proto_bytes(reqs):
    msg = pb.pb.GetRateLimitsReq()
    for r in reqs:
        msg.requests.append(pb.req_to_pb(r))
    return msg.SerializeToString()


def mesh_engine(clock, num_groups=1 << 10, chips=CHIPS):
    return IciEngine(
        IciEngineConfig(
            devices=jax.devices()[:chips], num_groups=num_groups,
            num_slots=1 << 11, batch_size=128, batch_wait_s=0.001,
            sync_wait_s=3600,
        ),
        now_fn=lambda: clock["now"],
    )


def one_chip_engine(clock, num_groups=1 << 10):
    return DeviceEngine(
        EngineConfig(num_groups=num_groups, ways=8, batch_size=128,
                     batch_wait_s=0.001),
        now_fn=lambda: clock["now"],
    )


def drive(eng, calls, path: str, stacked: bool = True) -> dict:
    """The calls through `path` ("columnar": check_columns on the wire
    columns; "object": the pump) with a counting Store attached; every
    answer, the Store, and each call's handed-over count beside its
    distinct ordinary keys. Without `stacked` no stacked Store shape is
    warm and every flush runs wave by wave."""
    store = CountingStore()
    attach_store(eng, store)
    if not stacked:
        eng._warm_store_stacks = ()
    clock = eng._test_clock
    out = {"store": store, "answers": [], "handed": []}
    for reqs in calls:
        clock["now"] += 10
        before = counts(eng)
        got = None
        if path == "columnar":
            got = eng.check_columns(
                wire.parse_requests(to_proto_bytes(reqs)), now=clock["now"]
            )
            assert got is not None, "the call left the columnar path"
            got = list(zip(*(a.tolist() for a in got)))
        else:
            got = [(int(r.status), r.limit, r.remaining, r.reset_time)
                   for r in eng.check_batch(
                       [dataclasses.replace(r) for r in reqs])]
        after = counts(eng)
        out["answers"].append(got)
        out["handed"].append((
            after["changed"] - before["changed"]
            + after["removed"] - before["removed"],
            len({r.hash_key() for r in reqs if not r.behavior & GLOBAL}),
        ))
    out["counts"] = counts(eng)
    out["skipped"] = out["counts"]["skipped"]
    return out


def reference(calls):
    ref = ReferenceStore()
    now = NOW
    answers = []
    for reqs in calls:
        now += 10
        answers.append([ref.apply(r, now) for r in reqs])
    return ref, answers


def run_on(make, calls, path, stacked=True):
    clock = {"now": NOW}
    eng = make(clock)
    eng._test_clock = clock
    try:
        return drive(eng, calls, path, stacked)
    finally:
        eng.close()


def shard_rows(eng) -> list:
    """[(shard index, {(key_hi, key_lo) of its used rows})] read from the
    sharded table's per-device slices, each unpacked on its own."""
    out = []
    shards = sorted(eng.table.data.addressable_shards,
                    key=lambda sh: sh.index[0].start or 0)
    for n, sh in enumerate(shards):
        wide = jax.tree.map(np.asarray, fused.unpack_table(
            fused.FusedTable(jnp.asarray(np.asarray(sh.data)))))
        used = wide.used
        out.append((n, set(zip(wide.key_hi[used].tolist(),
                               wide.key_lo[used].tolist()))))
    return out


def assert_store_is_the_references(store, ref):
    got = {k: entry_of(s) for k, s in store.data.items()}
    assert set(got) == set(ref.entries)
    for key, want in ref.entries.items():
        assert got[key] == want, key


@pytest.mark.parametrize("path", ["columnar", "object"])
def test_store_on_four_devices_equals_the_reference(path):
    """(i) columnar, (ii) object path: answers equal the oracle's, the
    Store's entries equal the reference model's key for key, every
    distinct key of a call is handed over once, nothing is skipped; one
    device gives the same Store."""
    calls = calls100(41, 12, keys=1500)
    ref, want = reference(calls)
    four = run_on(mesh_engine, calls, path)
    for n, (got, w) in enumerate(zip(four["answers"], want)):
        assert got == w, f"call {n}"
    assert_store_is_the_references(four["store"], ref)
    assert four["skipped"] == 0
    removed = four["counts"]["removed"]
    assert removed > 0, "the run exercises no remove"
    if path == "columnar":  # a columnar call is one flush (PR 36's identity)
        for handed, distinct in four["handed"]:
            assert handed == distinct
    else:  # the pump may split a call over flushes: a key once a flush
        assert all(h >= d for h, d in four["handed"])
    one = run_on(one_chip_engine, calls, path)
    assert one["answers"] == four["answers"]
    assert one["skipped"] == 0
    assert ({k: entry_of(s) for k, s in one["store"].data.items()}
            == {k: entry_of(s) for k, s in four["store"].data.items()})


def test_an_evicted_key_is_read_back_into_its_owners_shard():
    """(iii) 4,000 keys through 512 slots over four shards: every evicted
    key is read back (each Store hit is injected), no answer shows a
    fresh bucket, and a row that was read through sits in the slice of
    the chip that owns its group."""
    groups = 64
    calls = calls100(43, 30, keys=4000, reset_share=0.0)
    ref, want = reference(calls)
    clock = {"now": NOW}
    eng = mesh_engine(clock, num_groups=groups)
    eng._test_clock = clock
    try:
        out = drive(eng, calls, "columnar")
        evictions = eng.metrics.unexpired_evictions
        shards = shard_rows(eng)
    finally:
        eng.close()
    for n, (got, w) in enumerate(zip(out["answers"], want)):
        assert got == w, f"call {n}: a key showed a fresh bucket"
    c = out["counts"]
    assert evictions > 100 and c["hit"] > 100
    assert c["hit"] == c["injected"] == len(out["store"].answered)
    assert out["skipped"] == 0
    assert_store_is_the_references(out["store"], ref)
    # where the rows are: every resident key in its owner's slice, and
    # the keys that were read through among them
    per = groups // CHIPS
    read_back = {key_hash128(k) for k in out["store"].answered}
    assert len(shards) == CHIPS
    found = 0
    for n, keys in shards:
        assert keys, f"shard {n} holds nothing"
        for hi, lo in keys:
            owner = (lo % (1 << 64)) % groups // per
            assert owner == n, (hi, lo)
        found += len(keys & read_back)
    assert found > 50, "no read-through row is still resident"


@pytest.mark.parametrize("path", ["columnar", "object"])
def test_global_buckets_of_the_replica_tier_are_not_persisted(path):
    """(iv) a call mixing GLOBAL and ordinary items: only the ordinary
    keys reach the Store, on both paths, and the Store is never asked
    for a GLOBAL key."""
    calls = calls100(47, 8, keys=600, global_share=0.3)
    ordinary = [[r for r in reqs if not r.behavior & GLOBAL] for reqs in calls]
    assert all(len(o) < len(c) for o, c in zip(ordinary, calls))
    ref, _ = reference(ordinary)
    out = run_on(mesh_engine, calls, path)
    store = out["store"]
    assert_store_is_the_references(store, ref)
    assert not any(k.startswith("st4_g") for k in store.data)
    assert not any(k.startswith("st4_g") for k in store.asked)
    assert out["skipped"] == 0
    if path == "columnar":
        for handed, distinct in out["handed"]:
            assert handed == distinct
    # the ordinary items' answers are the oracle's (a GLOBAL item's is
    # its replica's, eventually consistent, and is not held here)
    now = NOW
    oracle = OracleEngine()
    for reqs, got in zip(calls, out["answers"]):
        now += 10
        for r, g in zip(reqs, got):
            if r.behavior & GLOBAL:
                continue
            w = oracle.decide(dataclasses.replace(r), now)
            assert g == (int(w.status), w.limit, w.remaining, w.reset_time)


def preload(keys: int, items: int = 75):
    """Calls that hit every ordinary key of calls100's keyspace once,
    as the benchmark's preload does: resident and persisted after."""
    reqs = [
        RateLimitReq(
            name="st4", unique_key=f"k{k:05d}", hits=1, limit=20,
            duration=3_600_000,
            algorithm=(Algorithm.TOKEN_BUCKET if k % 2 == 0
                       else Algorithm.LEAKY_BUCKET),
        )
        for k in range(keys)
    ]
    return [reqs[i:i + items] for i in range(0, keys, items)]


@pytest.mark.parametrize("path", ["columnar", "object"])
def test_the_sharded_tier_runs_resident_calls_stacked(path):
    """(ISSUE 45 f) 100-item calls over 150 keys, a fifth of the items
    GLOBAL: once a call's ordinary keys are all resident its sharded
    waves run stacked (one SPMD probe, decide and gather a run); the
    answers and the Store equal the per-wave twin's and the
    reference's, no row is skipped, no GLOBAL bucket is persisted and
    the guard reads 0."""
    calls = preload(150) + calls100(
        53, 6, keys=150, reset_share=0.0, global_share=0.2)
    ordinary = [[r for r in reqs if not r.behavior & GLOBAL] for reqs in calls]
    ref, _ = reference(ordinary)
    four = run_on(mesh_engine, calls, path)
    c = four["counts"]
    if path == "columnar":  # a call a flush: the preload's, then six runs
        assert (c["stacked"], c["per_wave"]) == (6, 2)
    else:  # the pump may split a call over flushes
        assert c["stacked"] >= 6
    assert c["surprises"] == c["skipped"] == 0
    assert_store_is_the_references(four["store"], ref)
    assert not any(k.startswith("st4_g") for k in four["store"].data)
    assert not any(k.startswith("st4_g") for k in four["store"].asked)
    twin = run_on(mesh_engine, calls, path, stacked=False)
    assert twin["counts"]["stacked"] == 0
    assert ({k: entry_of(s) for k, s in twin["store"].data.items()}
            == {k: entry_of(s) for k, s in four["store"].data.items()})
    # the ordinary items' answers are the per-wave twin's and the
    # oracle's (a GLOBAL item's is its replica's and is not held here)
    now = NOW
    oracle = OracleEngine()
    for reqs, got, tw in zip(calls, four["answers"], twin["answers"]):
        now += 10
        for r, g, t in zip(reqs, got, tw):
            if r.behavior & GLOBAL:
                continue
            w = oracle.decide(dataclasses.replace(r), now)
            assert g == t == (
                int(w.status), w.limit, w.remaining, w.reset_time)


def test_a_reset_lane_keeps_the_mesh_flush_per_wave():
    """(ISSUE 45 c) on the mesh as on one chip: with a RESET_REMAINING
    lane in it a flush of resident keys runs wave by wave."""
    calls = preload(40) + calls100(59, 4, keys=40, reset_share=0.0)
    calls += calls100(61, 2, keys=40, reset_share=0.2)
    assert all(any(r.behavior & RESET for r in reqs) for reqs in calls[5:])
    ref, want = reference(calls)
    four = run_on(mesh_engine, calls, "columnar")
    assert four["answers"] == want
    c = four["counts"]
    assert (c["stacked"], c["per_wave"]) == (4, 1 + 2)
    assert c["surprises"] == c["skipped"] == 0 and c["removed"] > 0
    assert_store_is_the_references(four["store"], ref)


def same_group_pair(num_groups):
    from gubernator_tpu.api.keys import group_of

    seen = {}
    for i in range(10_000):
        k = f"d{i}"
        g = group_of(key_hash128("st4_" + k)[1], num_groups)
        if g in seen:
            return seen[g], k
        seen[g] = k
    raise AssertionError("no two keys share a group")


@pytest.mark.parametrize("path", ["columnar", "object"])
@pytest.mark.parametrize("reset", [False, True], ids=["displaced", "freed"])
def test_a_key_displaced_between_its_own_waves_on_the_mesh(path, reset):
    """(vi) test_store_columnar_eviction's case (e) on four devices, one
    way a group: one flush [A, B, A] of two keys of one group. B's wave
    displaces A, and A's second wave continues from the row A's first
    wave left, which is still on the devices when it is asked for (the
    waves' rows are read after the engine lock, ISSUE 42): that one is
    read under the lock, a crossing. A RESET_REMAINING's freed row stays
    absent, and the Store's stale row is not read."""
    groups = 32  # x 1 way: four lines of eight slots, one a device
    ka, kb = same_group_pair(groups)

    def item(key, **kw):
        return RateLimitReq(name="st4", unique_key=key, hits=kw.pop("hits", 1),
                            limit=20, duration=3_600_000, **kw)

    def read_under_the_lock(eng):
        for ln in eng.metrics.store_wave_crossings.render_lines():
            if 'direction="d2h"' in ln:
                return float(ln.rpartition(" ")[2])
        raise KeyError("d2h")

    clock = {"now": NOW}
    eng = IciEngine(
        IciEngineConfig(
            devices=jax.devices()[:CHIPS], num_groups=groups, ways=1,
            num_slots=1 << 11, batch_size=128, batch_wait_s=0.001,
            sync_wait_s=3600,
        ),
        now_fn=lambda: clock["now"],
    )
    store = CountingStore()
    attach_store(eng, store)
    try:
        eng.check_batch([item(ka, hits=5)])
        assert store.data[f"st4_{ka}"].remaining == 15
        reqs = [item(ka, behavior=RESET) if reset else item(ka),
                item(kb), item(ka)]
        before, read0 = counts(eng), read_under_the_lock(eng)
        asked0 = len(store.asked)
        if path == "columnar":
            got = eng.check_columns(
                wire.parse_requests(to_proto_bytes(reqs)), now=NOW + 5)
            remaining = got[2].tolist()
        else:
            remaining = [r.remaining for r in eng.check_batch(reqs)]
        assert remaining == ([20, 19, 19] if reset else [14, 19, 13])
        after = counts(eng)
        # three probes' answers, the first wave's rows, and the two key
        # columns of the inject where A's row is seated again
        assert read_under_the_lock(eng) - read0 == 3 + 1 + (0 if reset else 2)
        # only B, never seen, is looked up: not A's stale row
        assert set(store.asked[asked0:]) == {f"st4_{kb}"}
        assert after["hit"] == before["hit"]
        assert after["skipped"] == 0
        assert store.data[f"st4_{ka}"].remaining == (19 if reset else 13)
        assert store.data[f"st4_{kb}"].remaining == 19
    finally:
        eng.close()


@pytest.mark.parametrize("make", [mesh_engine, one_chip_engine],
                         ids=["four", "one"])
def test_a_gather_that_reads_other_rows_is_counted_not_persisted(make):
    """(v) the silent skip is a number: with a kernel set whose gather
    returns every lane its neighbour's row, the lanes whose row is not
    their own are counted in gubernator_store_rows_skipped and nothing
    is persisted for them."""
    clock = {"now": NOW}
    eng = make(clock)
    store = CountingStore()
    attach_store(eng, store)
    real = eng.K.gather_rows
    eng.K = eng.K._replace(
        gather_rows=lambda t, s, from_output=False: jnp.roll(
            real(t, s, from_output), 1, axis=1)
    )
    reqs = [RateLimitReq(name="st4", unique_key=f"k{i}", hits=1, limit=20,
                         duration=3_600_000) for i in range(40)]
    try:
        assert skipped(eng) == 0
        got = eng.check_columns(
            wire.parse_requests(to_proto_bytes(reqs)), now=NOW
        )
        assert got is not None and (got[2] == 19).all()  # answers are right
        assert skipped(eng) == len(reqs)
        assert store.changed == 0 and not store.data
    finally:
        eng.close()


def test_the_mesh_programs_carry_names_and_phases_a_capture_can_select():
    """A reader selects the Store's programs by name (`gather_rows`,
    `probe_exists` in the program's name, as on one chip) and a capture
    groups their operations by phase."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gubernator_tpu.ops.layout import OPERAND_ROWS, OUT_STORE_ROWS, OUT_TOTALS
    from gubernator_tpu.parallel import mesh as pmesh

    mesh = pmesh.make_mesh(jax.devices()[:CHIPS])
    K = pmesh.make_mesh_kernels(mesh, "fused", 64, 8)
    table = K.create()
    repl = NamedSharding(mesh, P())
    B = 16
    operand = jax.device_put(np.zeros((OPERAND_ROWS, B), np.int64), repl)
    out = jax.device_put(
        np.zeros(OUT_STORE_ROWS * B + OUT_TOTALS, np.int64), repl)
    from gubernator_tpu.ops.kernels import get_raw_kernels

    raw = get_raw_kernels("fused")
    probe = pmesh._sharded_probe_exists(mesh, 16, 8, raw.probe_exists)
    gather = pmesh._sharded_gather_rows(mesh, 128, raw.gather_cols)
    texts = {
        "probe_exists": probe.lower(table, operand).as_text(debug_info=True),
        "gather_rows": gather.lower(
            table, out, from_output=True).as_text(debug_info=True),
    }
    for name, text in texts.items():
        assert f"jit_{name}_fn" in text, name
    for phase in ("owner_mask", "probe_local", "psum_probe"):
        assert phase in texts["probe_exists"], phase
    for phase in ("owner_mask", "store_rows_local", "psum_rows"):
        assert phase in texts["gather_rows"], phase
    decide = pmesh.make_sharded_decide(mesh, 64, 8, "fused")
    text = decide.lower(table, operand, with_store=True).as_text(debug_info=True)
    assert "global_slot" in text and "psum_merge" in text
    plain = decide.lower(table, operand, with_store=False).as_text(debug_info=True)
    assert "global_slot" not in plain  # the store-less program is what it was
