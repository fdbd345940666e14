"""The decide program's packed interface (ops/layout.py): one uploaded
operand in, one output vector out, bit-identical to the RequestBatch
entry it wraps — for both layouts at 4 and 8 ways, with and without
the store columns, through the paged kernels, and the mesh and replica
programs on faked devices."""

import dataclasses
import random

import jax
import numpy as np
import pytest

from gubernator_tpu.api.keys import group_of, key_hash128
from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.ops.encode import encode_batch
from gubernator_tpu.ops.kernels import (
    LAYOUTS,
    get_kernels,
    get_paged_kernels,
)
from gubernator_tpu.ops.layout import (
    OPERAND_ROWS,
    DecideOutput,
    RequestBatch,
    WaveOperand,
    output_struct,
    unpack_operand,
)
from gubernator_tpu.utils.gregorian import GREGORIAN_MINUTES

NOW = 1_753_700_000_000
NUM_GROUPS = 64  # tiny: full groups evict, so slot/evicted/freed carry values
WAYS = 4
B = 16
LANE_FIELDS = ("status", "limit", "remaining", "reset_time")
STORE_FIELDS = ("slot", "evicted_hi", "evicted_lo", "freed")
TOTALS = ("hits", "misses", "unexpired_evictions", "over_limit")


def corpus(seed, num_groups=NUM_GROUPS, steps=50, keys=120, global_=False):
    """The kernel fuzz suite's request mix (tests/test_kernel_fuzz.py:
    both algorithms, RESET/DRAIN/Gregorian, the adversarial domain) as
    waves of up to B lanes with distinct groups: [(RequestBatch, now)]."""
    rng = random.Random(seed)
    names = [f"acct:{i}" for i in range(keys)]
    now = NOW
    out = []
    for _ in range(steps):
        now += rng.choice([0, 1, 7, 500, 3000, 61_000, 10**7])
        reqs, used = [], set()
        for _ in range(rng.randrange(1, B + 1)):
            behavior = int(Behavior.GLOBAL) if global_ else 0
            if rng.random() < 0.08:
                behavior |= Behavior.RESET_REMAINING
            if rng.random() < 0.15:
                behavior |= Behavior.DRAIN_OVER_LIMIT
            greg = rng.random() < 0.10
            if greg:
                behavior |= Behavior.DURATION_IS_GREGORIAN
            r = RateLimitReq(
                name=rng.choice(["a", "b"]),
                unique_key=rng.choice(names),
                algorithm=rng.choice(
                    [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                ),
                behavior=behavior,
                duration=GREGORIAN_MINUTES if greg
                else rng.choice([0, 5, 1000, 30_000, 60_000, 2**40]),
                limit=rng.choice([0, 1, 2, 10, 2000, 2**31 - 1]),
                hits=rng.choice([-(2**30), -5, 0, 1, 1, 2, 5, 99, 3000]),
                burst=rng.choice([0, 0, 5, 30, 2**30]),
            )
            g = group_of(key_hash128(r.hash_key())[1], num_groups)
            if g not in used:
                used.add(g)
                reqs.append(r)
        out.append((encode_batch(reqs, now, num_groups, B), now))
    return out


def assert_same(got: DecideOutput, want: DecideOutput, with_store, where):
    fields = LANE_FIELDS + (STORE_FIELDS if with_store else ()) + TOTALS
    for f in fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
            err_msg=f"{where}: field {f}",
        )


def assert_same_table(K, a, b):
    wa, wb = K.to_wide(a), K.to_wide(b)
    for f in wa._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(wa, f)), np.asarray(getattr(wb, f)),
            err_msg=f"table field {f}",
        )


def test_operand_round_trip():
    """Every field survives the host views and the in-jit unpack with
    its own dtype, at the edges of each: the shared words do not bleed."""
    op = WaveOperand.zeros(4, waves=2)
    b = op.batch
    i64 = np.iinfo(np.int64)
    b.key_hi[:] = [[i64.min, -1, 0, i64.max]] * 2
    b.key_lo[:] = [[1, 2, 3, 4], [5, 6, 7, 8]]
    b.group[:] = [[-(2**31), -1, 0, 2**31 - 1]] * 2
    b.behavior[:] = [[2**31 - 1, 0, -1, -(2**31)]] * 2
    b.algo[:] = [[-128, -1, 1, 127]] * 2
    b.active[:] = [[True, False, True, False], [False, True, False, True]]
    for f in ("hits", "limit", "duration", "rate_num", "eff_duration",
              "greg_expire", "burst", "created_at"):
        getattr(b, f)[:] = [[i64.min, -7, 7, i64.max]] * 2
    op.home[:] = [[0, 1, 2, 3]] * 2
    op.stamp(NOW)
    assert op.buf.shape == (2, OPERAND_ROWS, 4)
    for w in range(2):
        got, home, now = jax.jit(unpack_operand)(op.wave(w).buf)
        assert int(now) == NOW
        np.testing.assert_array_equal(np.asarray(home), [0, 1, 2, 3])
        for f in RequestBatch._fields:
            want = getattr(b, f)[w]
            have = np.asarray(getattr(got, f))
            assert have.dtype == want.dtype, f
            np.testing.assert_array_equal(have, want, err_msg=f)
    # the tests' way in copies any RequestBatch of host arrays
    again = WaveOperand.of(op.wave(1).batch, NOW, op.wave(1).home)
    np.testing.assert_array_equal(again.buf, op.wave(1).buf)


@pytest.mark.parametrize("ways", [4, 8])
@pytest.mark.parametrize("with_store", [False, True])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_packed_entry_is_the_batch_entry(layout, with_store, ways):
    K = get_kernels(layout)
    groups = NUM_GROUPS * WAYS // ways  # as many slots, so groups still fill
    ta, tb = K.create(groups, ways), K.create(groups, ways)
    evicted = 0
    for i, (batch, now) in enumerate(corpus(7, num_groups=groups)):
        ta, want = K.decide(ta, batch, now, ways, with_store)
        tb, vec = K.decide_packed(
            tb, WaveOperand.of(batch, now).buf, ways, with_store
        )
        vec = np.asarray(vec)
        assert vec.dtype == np.int64
        assert vec.shape == ((8 if with_store else 4) * B + 4,)
        assert_same(output_struct(vec, with_store), want, with_store,
                    f"{layout} {ways} ways step {i}")
        evicted += int(np.count_nonzero(np.asarray(want.evicted_hi)))
    assert evicted > 0  # the store columns carried values
    assert_same_table(K, ta, tb)


@pytest.mark.parametrize("gpp", [8, 4])
def test_paged_packed_entry_is_the_batch_entry(gpp):
    layout, pages = "fused", NUM_GROUPS // gpp
    PK = get_paged_kernels(layout, NUM_GROUPS, WAYS, gpp, pages)
    pa, pb = PK.create(), PK.create()
    for lp in range(pages):  # every logical page resident
        z = np.int32(lp)
        pa, pb = PK.bind_page(pa, z, z), PK.bind_page(pb, z, z)
    for i, (batch, now) in enumerate(corpus(11)):
        pa, want = PK.decide(pa, batch, now, WAYS, True)
        pb, vec = PK.decide_packed(
            pb, WaveOperand.of(batch, now).buf, WAYS, True
        )
        assert_same(output_struct(vec, True), want, True,
                    f"paged {gpp} groups a page step {i}")
    assert_same_table(PK, pa, pb)


NDEV = 8


@pytest.mark.parametrize("n_dev", [NDEV, 4])
@pytest.mark.parametrize("layout", ["fused", "wide"])
def test_mesh_program_is_the_batch_entry(layout, n_dev):
    """The owner-sharded packed program over 8 (and 4) faked devices
    answers as the single-table RequestBatch entry does: every lane has
    one owner, so the psum of the packed vectors is that owner's answer."""
    from gubernator_tpu.parallel import mesh as pmesh

    groups = 8 * NDEV
    mesh = pmesh.make_mesh(jax.devices()[:n_dev])
    table = pmesh.create_sharded_table(mesh, groups, ways=WAYS, layout=layout)
    decide = pmesh.make_sharded_decide(mesh, groups, ways=WAYS, layout=layout)
    K = get_kernels(layout)
    flat = K.create(groups, WAYS)
    for i, (batch, now) in enumerate(corpus(17, num_groups=groups)):
        flat, want = K.decide(flat, batch, now, WAYS, False)
        table, vec = decide(table, WaveOperand.of(batch, now).buf)
        assert_same(output_struct(vec), want, False,
                    f"mesh {layout} x{n_dev} step {i}")
    assert_same_table(K, flat, table)


def test_replica_program_is_the_batch_entry():
    """The replica tier's packed program (the `home` row rides the
    operand): lane i is answered by device home[i]'s replica alone, so
    the answers are those of one flat table per home device deciding
    only its own lanes."""
    from gubernator_tpu.parallel import ici
    from gubernator_tpu.parallel import mesh as pmesh

    layout, groups = "fused", 8 * NDEV
    mesh = pmesh.make_mesh(jax.devices()[:NDEV])
    state = ici.create_ici_state(mesh, groups * WAYS, WAYS, layout=layout)
    decide = ici.make_replica_decide(mesh, groups * WAYS, WAYS, layout=layout)
    K = get_kernels(layout)
    flats = [K.create(groups, WAYS) for _ in range(NDEV)]
    rng = np.random.default_rng(19)
    for i, (batch, now) in enumerate(
        corpus(19, num_groups=groups, steps=25, global_=True)
    ):
        home = rng.integers(0, NDEV, B)
        state, vec = decide(state, WaveOperand.of(batch, now, home).buf)
        got = output_struct(vec)
        want = {f: 0 for f in LANE_FIELDS + TOTALS}
        for d in range(NDEV):
            mine = batch._replace(active=batch.active & (home == d))
            flats[d], o = K.decide(flats[d], mine, now, WAYS, False)
            for f in want:
                want[f] = want[f] + np.asarray(getattr(o, f)).astype(np.int64)
        for f in want:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)).astype(np.int64), want[f],
                err_msg=f"replica step {i}: field {f}",
            )
