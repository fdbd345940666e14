"""A run of waves is one launch, and a wave with no leaky lane runs no
division loop (ISSUE 35), at the level of the programs.

Half one: the packed entry given a stacked operand (W, OPERAND_ROWS, B)
applies the waves in order inside one program; outputs, totals and the
table afterwards are bit for bit those of W sequential launches, at
every depth the run is padded to, on one device and on the sharded mesh
(faked devices, tests/conftest.py).

Half two: `_leaky_paths` (all six division loops) sits under a
conditional on "any lane leaky"; all-token, all-leaky and mixed waves
equal the wide reference and models/oracle.py bit for bit, and the
traced program holds its loops only under that conditional.
"""

import dataclasses

import jax
import numpy as np
import pytest

from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.models.oracle import OracleEngine
from gubernator_tpu.api.keys import key_hash128_batch
from gubernator_tpu.ops.encode import encode_batch
from gubernator_tpu.ops.kernels import _packed_program, get_kernels
from gubernator_tpu.ops.layout import (
    OPERAND_ROWS,
    WaveOperand,
    split_output,
)

NOW = 1_753_700_000_000
NUM_GROUPS = 4096
WAYS = 8
DEPTHS = (8, 32)  # MeshEngine._wave_depths at the default max_waves
HOT = "hot"

MIXES = ("token", "leaky", "reset", "drain")


def _req(mix: str, key: str, wave: int, rng) -> RateLimitReq:
    """One item of a wave of `mix`; the hot key comes in every wave, so
    its state threads through the run (limit 1: its second hit is over
    the limit)."""
    hot = key == HOT
    algo = {
        "token": Algorithm.TOKEN_BUCKET,
        "leaky": Algorithm.LEAKY_BUCKET,
    }.get(mix, Algorithm.LEAKY_BUCKET if key[-1] in "13579" else Algorithm.TOKEN_BUCKET)
    if hot and mix in ("reset", "drain"):
        algo = Algorithm.TOKEN_BUCKET if mix == "reset" else Algorithm.LEAKY_BUCKET
    behavior = 0
    hits = 1
    if mix == "reset" and (wave % 3 == 2 if hot else rng.random() < 0.1):
        behavior |= int(Behavior.RESET_REMAINING)
    if mix == "drain":
        behavior |= int(Behavior.DRAIN_OVER_LIMIT)
        hits = int(rng.choice([1, 2, 9])) if not hot else (1 if wave % 4 else 9)
    return RateLimitReq(
        name="sw", unique_key=key, algorithm=algo, behavior=behavior,
        duration=60_000, limit=1 if hot else 5, hits=hits, burst=0,
        # leaky buckets leak between the waves of a run
        created_at=NOW + 700 * wave,
    )


def make_run(mix: str, waves: int, width: int, seed: int = 0):
    """[(requests, WaveOperand)] of `waves` scatter-disjoint waves: in
    each the hot key and a draw of others, one item a slot group."""
    rng = np.random.default_rng(seed)
    pool = [f"k{i}" for i in range(3 * width)]
    probe = [RateLimitReq(name="sw", unique_key=k) for k in [HOT] + pool]
    grp = key_hash128_batch([r.hash_key() for r in probe], NUM_GROUPS)[2]
    group_of = dict(zip([HOT] + pool, grp.tolist()))
    fill = max(width * 3 // 4, 2)
    run = []
    for w in range(waves):
        keys, seen = [HOT], {group_of[HOT]}
        for k in rng.permutation(pool):
            if len(keys) == fill:
                break
            if group_of[k] not in seen:
                seen.add(group_of[k])
                keys.append(str(k))
        reqs = [_req(mix, k, w, rng) for k in keys]
        batch = encode_batch(
            [dataclasses.replace(r) for r in reqs], NOW, NUM_GROUPS, width
        )
        run.append((reqs, WaveOperand.of(batch, NOW)))
    return run


def per_wave(K, table, run, ways=WAYS):
    outs = []
    for _reqs, op in run:
        table, out = K.decide_packed(table, jax.numpy.asarray(op.buf), ways)
        outs.append(np.asarray(out))
    return table, outs


def wide_leaves(K, table):
    return [np.asarray(x) for x in jax.tree.leaves(K.to_wide(table))]


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("width", [128, 256, 512, 1024])
@pytest.mark.parametrize("waves", [2, 3, 7, 32])
def test_stacked_run_equals_sequential_launches(waves, width, mix):
    K = get_kernels("fused")
    run = make_run(mix, waves, width, seed=waves * 1000 + width)
    table, want = per_wave(K, K.create(NUM_GROUPS, WAYS), run)
    want_table = wide_leaves(K, table)
    assert any(split_output(v)[1][3] for v in want), "no OVER_LIMIT in the run"
    for depth in (d for d in DEPTHS if d >= waves):
        stacked = WaveOperand.stacked([op for _r, op in run], depth)
        assert stacked.buf.shape == (depth, OPERAND_ROWS, width)
        table, out = K.decide_packed(
            K.create(NUM_GROUPS, WAYS), jax.numpy.asarray(stacked.buf), WAYS
        )
        out = np.asarray(out)
        assert out.shape == (depth, want[0].shape[0])
        for w in range(waves):
            np.testing.assert_array_equal(out[w], want[w], err_msg=f"wave {w}")
        # the waves that pad the run to its depth: nothing run, nothing
        # written, and the table is what the real waves left
        assert not out[waves:].any()
        for a, b in zip(wide_leaves(K, table), want_table):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("mix", MIXES)
def test_stacked_run_equals_sequential_launches_wide_reference(mix):
    K = get_kernels("wide")
    run = make_run(mix, 7, 128, seed=11)
    table, want = per_wave(K, K.create(NUM_GROUPS, WAYS), run)
    stacked = WaveOperand.stacked([op for _r, op in run], 8)
    table2, out = K.decide_packed(
        K.create(NUM_GROUPS, WAYS), jax.numpy.asarray(stacked.buf), WAYS
    )
    out = np.asarray(out)
    for w in range(7):
        np.testing.assert_array_equal(out[w], want[w])
    for a, b in zip(wide_leaves(K, table2), wide_leaves(K, table)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("waves", [2, 7])
def test_stacked_run_on_the_sharded_mesh(waves, mix):
    """Four shards, lanes of one wave owned by different shards: the
    loop runs inside the shard_map body and one psum merges the stacked
    output; equal to the mesh's sequential launches AND to one device's."""
    from gubernator_tpu.parallel import mesh as M

    mesh = M.make_mesh(jax.devices()[:4])
    MK = M.make_mesh_kernels(mesh, "fused", NUM_GROUPS, WAYS)
    run = make_run(mix, waves, 128, seed=waves)
    for _reqs, op in run:
        owners = set((op.batch.group[op.batch.active] // (NUM_GROUPS // 4)).tolist())
        assert owners == {0, 1, 2, 3}
    table, want = per_wave(MK, MK.create(), run)
    stacked = WaveOperand.stacked([op for _r, op in run], 8)
    table2, out = MK.decide_packed(MK.create(), jax.numpy.asarray(stacked.buf))
    out = np.asarray(out)
    K1 = get_kernels("fused")
    table1, want1 = per_wave(K1, K1.create(NUM_GROUPS, WAYS), run)
    for w in range(waves):
        np.testing.assert_array_equal(out[w], want[w])
        np.testing.assert_array_equal(out[w], want1[w])
    assert not out[waves:].any()
    for a, b, c in zip(
        wide_leaves(MK, table2), wide_leaves(MK, table), wide_leaves(K1, table1)
    ):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


# ---- half two: no leaky lane, no leaky path --------------------------------


@pytest.mark.parametrize("layout", ["fused", "wide"])
@pytest.mark.parametrize("mix", ["token", "leaky", "mixed"])
def test_waves_equal_the_oracle(mix, layout):
    """All-token waves take the branch that skips `_leaky_paths`,
    all-leaky and mixed ones the branch that runs it: every answer is
    the oracle's, and the two layouts leave the same table."""
    run = make_run("drain" if mix == "mixed" else mix, 7, 128, seed=5)
    algos = {r.algorithm for reqs, _ in run for r in reqs}
    assert algos == {
        "token": {Algorithm.TOKEN_BUCKET},
        "leaky": {Algorithm.LEAKY_BUCKET},
        "mixed": {Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET},
    }[mix]
    K = get_kernels(layout)
    table, outs = per_wave(K, K.create(NUM_GROUPS, WAYS), run)
    oracle = OracleEngine()
    for (reqs, _op), vec in zip(run, outs):
        rows, _tot = split_output(vec)
        for lane, r in enumerate(reqs):
            want = oracle.decide(dataclasses.replace(r), NOW)
            got = tuple(int(rows[i][lane]) for i in range(4))
            assert got == (
                int(want.status), int(want.limit), int(want.remaining),
                int(want.reset_time),
            ), (mix, r)
    ref = get_kernels("wide")
    ref_table, ref_outs = per_wave(ref, ref.create(NUM_GROUPS, WAYS), run)
    for a, b in zip(outs, ref_outs):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(wide_leaves(K, table), wide_leaves(ref, ref_table)):
        np.testing.assert_array_equal(a, b)


def _loops(jaxpr, under_cond=False, found=None):
    """[(under a conditional?, static trip count or None)] of every loop
    of a jaxpr, its sub-jaxprs included (a fori_loop of static bounds
    traces as a scan, one of traced bounds as a while)."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("while", "scan"):
            found.append((under_cond, eqn.params.get("length")))
        inner = under_cond or name == "cond"
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _loops(sub, inner, found)
    return found


@pytest.mark.parametrize("layout", ["fused", "wide"])
@pytest.mark.parametrize("depth", [None, 8])
def test_division_loops_sit_under_the_conditional(depth, layout):
    """The six 63-step division loops of `_leaky_paths` are the only
    loops of a wave, and all six sit under the conditional; the stacked
    program adds the one loop over its waves, of a traced trip count,
    outside it."""
    K = get_kernels(layout)
    table = K.create(64, WAYS)
    operand = WaveOperand.zeros(16, depth).buf
    jaxpr = jax.make_jaxpr(
        lambda t, op: _packed_program(layout)(t, op, ways=WAYS, with_store=False)
    )(table, operand)
    loops = _loops(jaxpr.jaxpr)
    assert [n for under, n in loops if under] == [63] * 6
    assert [n for under, n in loops if not under] == (
        [] if depth is None else [None]
    )
