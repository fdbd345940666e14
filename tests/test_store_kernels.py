"""The Store's two other programs exchange packed device arrays, as the
decide does (ISSUE 37): in every kernel set `gather_rows` returns ONE
(NCOLS, B) int64 array that `ops/layout.py wide_rows` views as the wide
struct on the host, it can take its slot column out of a `with_store`
output vector on the device, and `probe_exists` reads the wave's own
uploaded operand. Each is held against a reference that shares no code
with it: the table's `to_wide` image indexed in numpy, and a
three-column probe written out in numpy below.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from gubernator_tpu.ops.kernels import get_kernels, get_paged_kernels
from gubernator_tpu.ops.layout import (
    NCOLS,
    OUT_SLOT,
    SlotTable,
    WaveOperand,
    output_struct,
    split_output,
    wide_rows,
)

NOW = 1_753_700_000_000
WAYS, GROUPS, GPP = 4, 32, 8  # 128 slots, four pages of eight groups
N = GROUPS * WAYS
B = 16
I64_MIN, I64_MAX = -(1 << 63), (1 << 63) - 1

KERNEL_SETS = (
    "wide", "fused", "paged-wide", "paged-fused", "mesh", "mesh-paged",
)


def fuzzed_wide(rng) -> SlotTable:
    """N slots, every column over its whole range, every META field over
    its own: used or freed, token or leaky, all four status values."""
    def i64():
        return rng.integers(I64_MIN, I64_MAX, size=N, dtype=np.int64)

    return SlotTable(
        key_hi=i64(), key_lo=i64(),
        used=rng.integers(0, 2, size=N).astype(bool),
        algo=rng.integers(0, 2, size=N).astype(np.int8),
        status=rng.integers(0, 4, size=N).astype(np.int8),
        limit=i64(), duration=i64(), remaining=i64(), stamp=i64(),
        expire_at=i64(), invalid_at=i64(), burst=i64(),
        lru=rng.integers(0, 1 << 59, size=N, dtype=np.int64),
    )


class Loaded:
    """One kernel set holding a logical wide image: K, the table, and
    how an operand gets to the device."""

    def __init__(self, kind: str, wide: SlotTable):
        layout = "wide" if kind.endswith("wide") else "fused"
        self.put = jnp.asarray
        mesh = None
        if kind.startswith("mesh"):
            from gubernator_tpu.parallel import mesh as pmesh

            mesh = pmesh.make_mesh(jax.devices()[:4])
            repl = NamedSharding(mesh, P())
            self.put = lambda a: jax.device_put(np.asarray(a), repl)
        if "paged" not in kind:
            self.K = (
                get_kernels(layout) if mesh is None
                else pmesh.make_mesh_kernels(mesh, layout, GROUPS, WAYS)
            )
            self.table = self.K.from_wide(jax.tree.map(jnp.asarray, wide))
            return
        pages = GROUPS // GPP
        self.K = PK = (
            get_paged_kernels(layout, GROUPS, WAYS, GPP, pages)
            if mesh is None
            else pmesh.make_mesh_kernels(
                mesh, layout, GROUPS, WAYS, page_groups=GPP, page_budget=pages
            )
        )
        frame = np.roll(np.arange(pages), 1)  # logical page -> frame
        pt = PK.create()
        for lp in range(pages):
            rows = jax.tree.map(
                lambda a: jnp.asarray(a[lp * GPP * WAYS:(lp + 1) * GPP * WAYS]),
                wide,
            )
            pt = PK.write_page(pt, np.int32(lp), np.int32(frame[lp]), rows)
        self.table = pt


@pytest.fixture(scope="module")
def wide():
    return fuzzed_wide(np.random.default_rng(37))


def image(K, table) -> SlotTable:
    """The table's own to_wide image (physical for a paged one) on the
    host: the reference a gather is held against."""
    return jax.tree.map(np.asarray, K.to_wide(table))


def rows_reference(img: SlotTable, slots) -> SlotTable:
    valid = slots < N
    safe = np.clip(slots, 0, N - 1)
    return jax.tree.map(
        lambda a: np.where(valid, a[safe], np.zeros((), a.dtype)), img
    )


def assert_same_struct(got: SlotTable, want: SlotTable):
    for f in SlotTable._fields:
        g, w = getattr(got, f), getattr(want, f)
        assert isinstance(g, np.ndarray), f
        assert g.dtype == w.dtype and g.shape == w.shape, f
        np.testing.assert_array_equal(g, w, err_msg=f)


@pytest.mark.parametrize("kind", KERNEL_SETS)
def test_packed_rows_through_the_host_view_equal_the_struct(kind, wide):
    """(a) live rows, freed rows, token and leaky, every META field, and
    a slot past the table (a padding lane's), which reads zeros."""
    ld = Loaded(kind, wide)
    rng = np.random.default_rng(1)
    slots = rng.permutation(N)[:B].astype(np.int64)
    slots[3] = slots[11] = N  # padding lanes
    img = image(ld.K, ld.table)
    used = img.used[np.clip(slots, 0, N - 1)]
    assert used.any() and not used.all()  # live and freed rows both
    packed = ld.K.gather_rows(ld.table, ld.put(slots))
    assert packed.shape == (NCOLS, B) and packed.dtype == jnp.int64
    got = wide_rows(np.asarray(packed))
    want = rows_reference(img, slots)
    assert_same_struct(got, want)
    assert not any(getattr(got, f)[3] or getattr(got, f)[11]
                   for f in SlotTable._fields)
    for f in ("algo", "status", "used", "lru"):  # all of META was in play
        assert len(np.unique(getattr(want, f))) > 1, f


def probe_reference(wide: SlotTable, hi, lo, group, active, now):
    """The three-column probe, in numpy over the logical image: a lane
    exists iff it is active and a way of its group is used, not expired,
    not invalidated, and holds its key."""
    out = np.zeros(len(hi), bool)
    for i in range(len(hi)):
        for s in range(group[i] * WAYS, (group[i] + 1) * WAYS):
            inv = wide.invalid_at[s]
            live = (
                wide.used[s]
                and not wide.expire_at[s] < now
                and not (inv != 0 and inv < now)
                and wide.key_hi[s] == hi[i]
                and wide.key_lo[s] == lo[i]
            )
            out[i] |= bool(active[i] and live)
    return out


def probe_case(wide: SlotTable):
    """An image and a wave over it whose lanes meet every branch of the
    probe: live, expired, invalidated, invalidation not yet due, freed,
    absent, and padding lanes whose key would be found."""
    w = SlotTable(*(a.copy() for a in wide))
    rng = np.random.default_rng(2)
    groups = rng.permutation(GROUPS)[:B]
    slot = groups * WAYS + rng.integers(0, WAYS, size=B)
    w.used[slot] = True
    w.expire_at[slot] = NOW + 60_000
    w.invalid_at[slot] = 0
    kinds = ["live", "expired", "invalidated", "invalid_later", "freed",
             "absent", "padding", "live"] * 2
    for i, k in enumerate(kinds):
        s = slot[i]
        if k == "expired":
            w.expire_at[s] = NOW - 1
        elif k == "invalidated":
            w.invalid_at[s] = NOW - 1
        elif k == "invalid_later":
            w.invalid_at[s] = NOW + 5
        elif k == "freed":
            w.used[s] = False
    batch = WaveOperand.zeros(B).batch
    batch.key_hi[:] = w.key_hi[slot]
    batch.key_lo[:] = w.key_lo[slot]
    batch.group[:] = groups
    batch.active[:] = [k != "padding" for k in kinds]
    for i, k in enumerate(kinds):
        if k == "absent":
            batch.key_lo[i] ^= 1
    want = [k in ("live", "invalid_later") for k in kinds]
    return w, batch, np.array(want)


@pytest.mark.parametrize("kind", KERNEL_SETS)
def test_probe_of_the_operand_equals_the_three_column_probe(kind, wide):
    """(b) the probe unpacks key_hi, key_lo, group and now from the
    wave's uploaded operand; padding lanes answer False."""
    w, batch, want = probe_case(wide)
    ld = Loaded(kind, w)
    ref = probe_reference(
        w, batch.key_hi, batch.key_lo, batch.group, batch.active, NOW
    )
    np.testing.assert_array_equal(ref, want)  # the case is what it says
    operand = ld.put(WaveOperand.of(batch, NOW).buf)
    got = np.asarray(ld.K.probe_exists(ld.table, operand, WAYS))
    assert got.dtype == bool and got.shape == (B,)
    np.testing.assert_array_equal(got, want)
    # `now` rides the operand: a second later the far invalidation is due
    later = ld.put(WaveOperand.of(batch, NOW + 1_000).buf)
    got = np.asarray(ld.K.probe_exists(ld.table, later, WAYS))
    np.testing.assert_array_equal(
        got, probe_reference(w, batch.key_hi, batch.key_lo, batch.group,
                             batch.active, NOW + 1_000),
    )
    assert got.sum() == want.sum() - 2


@pytest.mark.parametrize("kind", KERNEL_SETS)
def test_gather_fed_from_the_decide_output_on_the_device(kind, wide):
    """(c) gather_rows(table, out, True) takes OUT_SLOT out of the
    `with_store` vector inside its program and gives what gather_rows of
    the host-sliced slot row gives: the rows as that decide left them."""
    ld = Loaded(kind, wide)
    rng = np.random.default_rng(3)
    lanes = 11  # the rest is padding
    batch = WaveOperand.zeros(B).batch
    batch.key_hi[:lanes] = rng.integers(1, I64_MAX, size=lanes)
    batch.key_lo[:lanes] = rng.integers(1, I64_MAX, size=lanes)
    batch.group[:lanes] = rng.permutation(GROUPS)[:lanes]
    batch.algo[:lanes] = np.arange(lanes) % 2
    for f in ("limit", "burst"):
        getattr(batch, f)[:lanes] = 10
    for f in ("duration", "rate_num", "eff_duration"):
        getattr(batch, f)[:lanes] = 60_000
    batch.hits[:lanes] = 1
    batch.created_at[:lanes] = NOW
    batch.active[:lanes] = True
    operand = ld.put(WaveOperand.of(batch, NOW).buf)
    table, out = ld.K.decide_packed(ld.table, operand, WAYS, True)
    fed = np.asarray(ld.K.gather_rows(table, out, True))
    slots = split_output(np.asarray(out), True)[0][OUT_SLOT]
    sliced = np.asarray(ld.K.gather_rows(table, ld.put(slots)))
    np.testing.assert_array_equal(fed, sliced)
    rows = wide_rows(fed)
    assert_same_struct(rows, rows_reference(image(ld.K, table), slots))
    # the lanes' own rows, as the decide wrote them; padding reads zeros.
    # On a sharded table too: the slot column names rows of the whole
    # table (each owner rebases its own before the psum), and the gather
    # reads each from the shard that owns it.
    assert (slots[:lanes] < N).all() and (slots[lanes:] == N).all()
    assert rows.used[:lanes].all() and not rows.used[lanes:].any()
    np.testing.assert_array_equal(rows.key_hi, batch.key_hi)
    np.testing.assert_array_equal(rows.key_lo, batch.key_lo)
    np.testing.assert_array_equal(rows.algo, batch.algo)
    np.testing.assert_array_equal(rows.lru[:lanes], NOW)
    o = output_struct(out, True)
    token = batch.algo[:lanes] == 0
    np.testing.assert_array_equal(
        rows.remaining[:lanes][token], o.remaining[:lanes][token]
    )


def test_the_host_view_is_views_of_the_one_array():
    """wide_rows copies no int64 column: what the engine keeps of a wave
    is the one array it read."""
    packed = np.arange(NCOLS * B, dtype=np.int64).reshape(NCOLS, B)
    rows = wide_rows(packed)
    for f in ("key_hi", "key_lo", "limit", "duration", "remaining", "stamp",
              "expire_at", "invalid_at", "burst"):
        assert np.shares_memory(getattr(rows, f), packed), f
    assert rows.used.dtype == bool and rows.algo.dtype == np.int8
