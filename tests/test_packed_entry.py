"""The decide program's packed interface (ops/layout.py): one uploaded
operand in, one output vector out, the one entry of every kernel set.
The serving layout's program is held to the reference layout's (`wide`)
at 4 and 8 ways, with and without the store columns, a launch a wave
and as one stacked run; the paged kernels, and the mesh and replica
programs on faked devices, are held to the flat program."""

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
    batch_entry,
    output_struct,
    unpack_operand,
)
from gubernator_tpu.utils.gregorian import GREGORIAN_MINUTES
from tests.test_kernel_fuzz import RUNS, decide_seq, packed

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


def flat_entry(layout, ways, with_store=False):
    """The flat single-table program of `layout` under the RequestBatch
    signature: what every other program here is held to."""
    return batch_entry(packed(get_kernels(layout), ways, with_store), with_store)


def assert_same_table(K, a, b, Kb=None):
    wa, wb = K.to_wide(a), (Kb or K).to_wide(b)
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
@pytest.mark.parametrize("run", RUNS)
def test_fused_program_is_the_wide_program(run, with_store, ways):
    """The serving layout's launch, a wave at a time or the corpus as one
    stacked run, answers lane for lane (store columns and totals too) as
    the reference layout's does, and leaves the same table."""
    W, F = get_kernels("wide"), get_kernels("fused")
    groups = NUM_GROUPS * WAYS // ways  # as many slots, so groups still fill
    steps = corpus(7, num_groups=groups)
    wide_step = flat_entry("wide", ways, with_store)
    tw, wants = W.create(groups, ways), []
    for batch, now in steps:
        tw, want = wide_step(tw, batch, now)
        wants.append(want)

    def fused(t, op):
        t, vec = F.decide_packed(t, op, ways, with_store)
        assert vec.dtype == np.int64
        assert vec.shape[-1] == (8 if with_store else 4) * B + 4
        return t, vec

    tf, gots = decide_seq(fused, F.create(groups, ways), steps, run, with_store)
    evicted = 0
    for i, (got, want) in enumerate(zip(gots, wants)):
        assert_same(got, want, with_store, f"{run} {ways} ways step {i}")
        if with_store:
            evicted += int(np.count_nonzero(want.evicted_hi))
    assert evicted > 0 or not with_store  # the store columns carried values
    assert_same_table(W, tw, tf, F)


@pytest.mark.parametrize("gpp", [8, 4])
def test_paged_program_is_the_flat_program(gpp):
    layout, pages = "fused", NUM_GROUPS // gpp
    K = get_kernels(layout)
    PK = get_paged_kernels(layout, NUM_GROUPS, WAYS, gpp, pages)
    flat, pt = K.create(NUM_GROUPS, WAYS), PK.create()
    for lp in range(pages):  # every logical page resident, in place
        pt = PK.bind_page(pt, np.int32(lp), np.int32(lp))
    flat_step = flat_entry(layout, WAYS, True)
    for i, (batch, now) in enumerate(corpus(11)):
        flat, want = flat_step(flat, batch, now)
        pt, vec = PK.decide_packed(
            pt, WaveOperand.of(batch, now).buf, WAYS, True
        )
        assert_same(output_struct(vec, True), want, True,
                    f"paged {gpp} groups a page step {i}")
    assert_same_table(K, flat, pt, PK)


NDEV = 8


@pytest.mark.parametrize("n_dev", [NDEV, 4])
@pytest.mark.parametrize("layout", ["fused", "wide"])
def test_mesh_program_is_the_flat_program(layout, n_dev):
    """The owner-sharded packed program over 8 (and 4) faked devices
    answers as the single-table program does: every lane has one owner,
    so the psum of the packed vectors is that owner's answer."""
    from gubernator_tpu.parallel import mesh as pmesh

    groups = 8 * NDEV
    mesh = pmesh.make_mesh(jax.devices()[:n_dev])
    table = pmesh.create_sharded_table(mesh, groups, ways=WAYS, layout=layout)
    decide = pmesh.make_sharded_decide(mesh, groups, ways=WAYS, layout=layout)
    K = get_kernels(layout)
    flat, flat_step = K.create(groups, WAYS), flat_entry(layout, WAYS)
    for i, (batch, now) in enumerate(corpus(17, num_groups=groups)):
        flat, want = flat_step(flat, batch, now)
        table, vec = decide(table, WaveOperand.of(batch, now).buf)
        assert_same(output_struct(vec), want, False,
                    f"mesh {layout} x{n_dev} step {i}")
    assert_same_table(K, flat, table)


def test_replica_program_is_the_flat_program():
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
    flat_step = flat_entry(layout, WAYS)
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
            flats[d], o = flat_step(flats[d], mine, now)
            for f in want:
                want[f] = want[f] + np.asarray(getattr(o, f)).astype(np.int64)
        for f in want:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)).astype(np.int64), want[f],
                err_msg=f"replica step {i}: field {f}",
            )
