"""The fused table as 32-bit words (ops/fused.py): the two helpers and the
interchange are exact, and no program that serves a wave does work that
grows with the table. Then the registry of the two layouts
(ops/kernels.py): what it says of each, and what engines take from it.

The structural cases are what a CPU run can give: counts read off the
traced program. The times are the chip's (PERF.md §6, PR 29).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gubernator_tpu.models.bucket import FIXED_SHIFT
from gubernator_tpu.ops import fused as F
from gubernator_tpu.ops.inject import InjectBatch
from gubernator_tpu.api.types import Behavior, RateLimitReq, Status
from gubernator_tpu.ops.kernels import (
    BYTES_PER_SLOT,
    LAYOUTS,
    get_kernels,
    get_raw_kernels,
    packed_decide,
)
from gubernator_tpu.ops.layout import (
    OPERAND_ROWS,
    OUT_STORE_ROWS,
    OUT_TOTALS,
    SlotTable,
)
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig

I64_MIN, I64_MAX = -(1 << 63), (1 << 63) - 1

EDGES = {
    "zero": 0,
    "minus_one": -1,
    "min": I64_MIN,
    "max": I64_MAX,
    "two_to_32": 1 << 32,
    "minus_two_to_32": -(1 << 32),
    "low_word_full": (1 << 32) - 1,
    "negative_q44_20_remainder": -((7 << FIXED_SHIFT) + 12345),
    "key_hash_in_the_top_range": 0xF3A1_9C55_0B7E_D201 - (1 << 64),
}


@pytest.mark.parametrize("name", list(EDGES))
def test_split_join_round_trips_an_edge_value(name):
    v = EDGES[name]
    lo, hi = F.split(jnp.asarray([v], dtype=jnp.int64))
    assert lo.dtype == hi.dtype == jnp.uint32
    assert int(lo[0]) == v & 0xFFFFFFFF
    assert int(hi[0]) == (v >> 32) & 0xFFFFFFFF
    assert int(F.join(lo, hi)[0]) == v


def test_slot_words_round_trip_and_pad_with_zeros():
    rng = np.random.default_rng(3)
    rows = rng.integers(I64_MIN, I64_MAX, size=(5, 4, F.NCOLS), dtype=np.int64)
    rows[0, 0, : len(EDGES)] = list(EDGES.values())
    words = F.split_words(jnp.asarray(rows))
    assert words.shape == (5, 4, F.SLOT_WORDS) and words.dtype == jnp.uint32
    assert not np.asarray(words[..., F.NWORDS:]).any()
    np.testing.assert_array_equal(np.asarray(F.join_words(words)), rows)


def fuzzed_wide(rng, shape) -> SlotTable:
    """Every column over its whole range: any int64 in the int64
    columns, negative remainders and INV marks among them."""
    def i64():
        return rng.integers(I64_MIN, I64_MAX, size=shape, dtype=np.int64)

    return SlotTable(
        key_hi=i64(), key_lo=i64(),
        used=rng.integers(0, 2, size=shape).astype(bool),
        algo=rng.integers(0, 2, size=shape).astype(np.int8),
        status=rng.integers(0, 4, size=shape).astype(np.int8),
        limit=i64(), duration=i64(), remaining=i64(), stamp=i64(),
        expire_at=i64(), invalid_at=i64(), burst=i64(),
        # the meta word keeps the stamp's low 60 bits (ops/fused.py META)
        lru=rng.integers(0, 1 << 59, size=shape, dtype=np.int64),
    )


def assert_same_tree(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize(
    "shape", [(64,), (6,), (3, 64), (2, 12)],
    ids=["flat", "flat_tiny", "stacked", "stacked_tiny"],
)
def test_the_interchange_is_the_identity_both_ways(shape):
    wide = jax.tree.map(
        jnp.asarray, fuzzed_wide(np.random.default_rng(11), shape)
    )
    table = F.pack_table(wide)
    assert table.data.dtype == jnp.uint32
    assert table.num_slots == shape[-1]
    assert table.data.shape[:-2] == shape[:-1]
    assert_same_tree(F.unpack_table(table), wide)  # to_wide(from_wide(w)) == w
    assert_same_tree(F.pack_table(F.unpack_table(table)), table)
    # the host views are the wide columns, int64
    for view in ("key_hi", "key_lo", "expire_at", "remaining", "used"):
        np.testing.assert_array_equal(
            np.asarray(getattr(table, view)), np.asarray(getattr(wide, view))
        )
    assert table.key_hi.dtype == jnp.int64


@pytest.mark.parametrize("n,ways", [(64, 8), (64, 4), (48, 16), (24, 3)])
def test_groups_taken_alone_and_put_back(n, ways):
    """The sync tick's compaction (RawKernels.take_groups/put_groups):
    the groups come out in the order asked for, go back where they were,
    and a group index past the end writes nothing."""
    rng = np.random.default_rng(n + ways)
    wide = jax.tree.map(jnp.asarray, fuzzed_wide(rng, (n,)))
    RK = get_raw_kernels("fused")
    table = RK.from_wide(wide)
    groups = n // ways
    gids = jnp.asarray(rng.permutation(groups)[: max(1, groups // 2)])
    slots = (np.asarray(gids)[:, None] * ways + np.arange(ways)).reshape(-1)
    part = RK.take_groups(table, gids, ways)
    assert_same_tree(RK.to_wide(part), jax.tree.map(lambda a: a[slots], wide))
    # as the tick does it: through the wide view and back, sentinel included
    again = RK.from_wide(RK.to_wide(part))
    sentinel = jnp.asarray(np.append(np.asarray(gids)[:-1], groups))
    back = RK.put_groups(RK.create(groups, ways), sentinel, ways, again)
    kept = slots[: -ways]
    want = jax.tree.map(
        lambda a: jnp.zeros_like(a).at[kept].set(a[kept]), wide
    )
    got = RK.to_wide(back)
    np.testing.assert_array_equal(np.asarray(got.key_hi), np.asarray(want.key_hi))
    np.testing.assert_array_equal(
        np.asarray(got.remaining), np.asarray(want.remaining)
    )


# ---- structure: nothing in a wave's program follows the table ---------------

B, WAYS = 16, 8
# what may take a table-sized value: the accesses (the table's gather
# and scatter-add; a gather and a scatter on the replica tier's pending
# words), the wrappers that hand the table through, and a new view of
# the same elements (`x[0]` / `x[None]` on a device's shard)
ACCESS = {"gather", "scatter-add", "scatter"}
PASS_THROUGH = {"pjit", "jit", "shard_map"}
SAME_ELEMENTS = {"slice", "squeeze", "reshape", "broadcast_in_dim"}


def sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (list, tuple)) else (v,):
            if hasattr(x, "eqns"):
                yield x
            elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr


def walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in sub_jaxprs(eqn):
            yield from walk(sub)


def census_of(fn, args, table_elems):
    """(number of equations, primitives that take a table-sized value)
    of the traced program, and no table-sized value is 64 bits wide."""
    closed = jax.make_jaxpr(fn)(*args)
    n_eqns, takers = 0, set()
    for eqn in walk(closed.jaxpr):
        n_eqns += 1
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            if int(np.prod(aval.shape, dtype=np.int64)) < table_elems:
                continue
            assert aval.dtype.itemsize < 8, (
                f"{eqn.primitive.name}: a table-sized 64-bit value "
                f"{aval.dtype}{list(aval.shape)}"
            )
            if v in eqn.invars:
                name = eqn.primitive.name
                if name in SAME_ELEMENTS and all(
                    o.aval.size == aval.size for o in eqn.outvars
                ):
                    continue
                takers.add(name)
    return n_eqns, takers


def flat_case(n):
    table = F.FusedTable.create(n // WAYS, WAYS)
    program = packed_decide("fused")
    operand = jnp.zeros((OPERAND_ROWS, B), dtype=jnp.int64)
    return lambda t, o: program(t, o, WAYS), (table, operand)


def inject_case(n):
    table = F.FusedTable.create(n // WAYS, WAYS)
    items = jax.tree.map(jnp.asarray, InjectBatch.zeros(B))
    return lambda t, i: F.inject_fused(t, i, 0, ways=WAYS), (table, items)


def probe_case(n):
    table = F.FusedTable.create(n // WAYS, WAYS)
    operand = jnp.zeros((OPERAND_ROWS, B), dtype=jnp.int64)
    return lambda t, o: F.probe_exists_fused(t, o, ways=WAYS), (table, operand)


def rows_case(n):
    table = F.FusedTable.create(n // WAYS, WAYS)
    # the Store sequence's variant: the slot column read out of a
    # `with_store` output vector, one packed (NCOLS, B) array back
    out = jnp.zeros((OUT_STORE_ROWS * B + OUT_TOTALS,), dtype=jnp.int64)
    return lambda t, o: F.gather_rows_fused(t, o, True), (table, out)


NDEV = 4


def mesh_case(n):
    from gubernator_tpu.parallel import mesh as pmesh

    mesh = pmesh.make_mesh(jax.devices()[:NDEV])
    groups = NDEV * n // WAYS
    table = pmesh.create_sharded_table(mesh, groups, ways=WAYS)
    decide = pmesh.make_sharded_decide(mesh, groups, ways=WAYS)
    operand = jnp.zeros((OPERAND_ROWS, B), dtype=jnp.int64)
    return decide, (table, operand)


def replica_case(n):
    from gubernator_tpu.parallel import ici
    from gubernator_tpu.parallel import mesh as pmesh

    mesh = pmesh.make_mesh(jax.devices()[:NDEV])
    state = ici.create_ici_state(mesh, n, WAYS)
    decide = ici.make_replica_decide(mesh, n, WAYS)
    operand = jnp.zeros((OPERAND_ROWS, B), dtype=jnp.int64)
    return decide, (state, operand)


CASES = {
    "packed_decide": flat_case,
    "inject_fused": inject_case,
    "probe_exists_fused": probe_case,
    "gather_rows_fused": rows_case,
    "mesh_decide": mesh_case,
    "replica_decide": replica_case,
}


@pytest.mark.parametrize("name", list(CASES))
def test_no_work_follows_the_table(name):
    """At two table sizes the traced program is the same equations, no
    value that holds a slot of every line is 64 bits wide, and only the
    gather and the scatter take the table."""
    seen = []
    for n in (1 << 13, 1 << 16):  # a wave's lines hold 4,096 words
        fn, args = CASES[name](n)
        # the smallest thing that holds a word of every slot a device has
        n_eqns, takers = census_of(fn, args, n)
        assert takers & ACCESS, f"{name}: the table is never read or written"
        assert takers <= ACCESS | PASS_THROUGH, (
            f"{name} at {n} slots: {sorted(takers - ACCESS - PASS_THROUGH)} "
            "take a table-sized value"
        )
        seen.append(n_eqns)
    assert seen[0] == seen[1], f"{name}: {seen} equations at the two sizes"


# ---------------------------------------------------------------------------
# The registry of the two layouts, and the engine seams that read it.

NOW = 1_753_700_000_000


def test_round_trip_through_every_layout():
    """The wide row format is the canonical interchange: converting the
    SAME snapshot through each layout's from_wide/to_wide must be the
    identity, which is what makes Loader files portable."""
    wide = jax.tree.map(
        jnp.asarray, fuzzed_wide(np.random.default_rng(13), (256,))
    )
    for layout in LAYOUTS:
        K = get_kernels(layout)
        assert_same_tree(K.to_wide(K.from_wide(wide)), wide)


def test_bytes_per_slot_registry():
    # The registry drives engine table-size gates: one entry a layout,
    # and every facade reports its own.
    assert BYTES_PER_SLOT == {"wide": 83, "fused": 80}
    for layout in LAYOUTS:
        assert get_kernels(layout).bytes_per_slot == BYTES_PER_SLOT[layout]


def mk(key="k", **kw):
    kw.setdefault("name", "t")
    kw.setdefault("duration", 60_000)
    kw.setdefault("limit", 10)
    kw.setdefault("hits", 1)
    return RateLimitReq(unique_key=key, **kw)


def _engine(layout, **kw):
    kw.setdefault("num_groups", 1 << 10)
    kw.setdefault("batch_size", 64)
    kw.setdefault("batch_wait_s", 0.002)
    return DeviceEngine(EngineConfig(layout=layout, **kw), now_fn=lambda: NOW)


@pytest.mark.parametrize("src,dst", [("fused", "wide"), ("wide", "fused")])
def test_snapshot_portable_across_layouts(src, dst):
    """Counters survive a snapshot/restore across DIFFERENT table
    layouts — the Loader interchange stays the wide row format."""
    a = _engine(src)
    try:
        a.check_batch([mk(key="port", hits=7), mk(key="other", hits=3)])
        snap = a.snapshot()
    finally:
        a.close()
    b = _engine(dst)
    try:
        b.restore(snap)
        out = b.check_batch([mk(key="port", hits=0), mk(key="other", hits=2)])
        assert out[0].remaining == 3  # 10 - 7, carried across layouts
        assert out[1].remaining == 5  # 10 - 3 - 2, counter continued
    finally:
        b.close()


def test_warm_buckets_oversized_table_skips(monkeypatch):
    """The bucket-warm ladder compiles against a THROWAWAY table copy;
    beyond the scratch budget it is skipped (runtime/engine.py
    _warm_buckets) and only batch_size stays warm. Pin the interaction:
    a single NO_BATCHING request on such an engine is still served —
    through a batch_size-wide dispatch, never a mid-request JIT stall."""
    monkeypatch.setattr(DeviceEngine, "_WARM_TABLE_BUDGET", 1)
    eng = _engine("fused", batch_size=512, fast_buckets=True)
    try:
        # The warmer must exit promptly (it skipped), leaving only the
        # always-warm batch_size shape.
        assert eng.wait_warm(timeout_s=60.0)
        assert eng._warm_shapes == (512,)
        rl = eng.check_batch([mk(behavior=Behavior.NO_BATCHING)])[0]
        assert (rl.status, rl.remaining) == (Status.UNDER_LIMIT, 9)
    finally:
        eng.close()


def test_warm_buckets_budget_uses_layout_bytes():
    """The gate is sized by the LAYOUT's resident bytes/slot: a fused
    table (80 B/slot) fits a budget the wide layout (83 B/slot) would
    blow, so the ladder still warms where the bytes actually allow it."""
    budget = DeviceEngine._WARM_TABLE_BUDGET
    groups = budget // (8 * BYTES_PER_SLOT["fused"])  # fused under, wide over
    assert groups * 8 * BYTES_PER_SLOT["fused"] <= budget
    assert groups * 8 * BYTES_PER_SLOT["wide"] > budget
