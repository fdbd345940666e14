"""Randomized differential fuzz of the owner-sharded mesh decide against
the sequential oracle: random request sequences (behaviors, algorithms,
time advances) batched with the assembler's distinct-group rule, decided
across an 8-device mesh, must match the oracle exactly."""

import dataclasses
import random

import jax
import numpy as np
import pytest

from gubernator_tpu.api.keys import group_of, key_hash128
from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.models.oracle import OracleEngine
from gubernator_tpu.ops.encode import encode_batch
from gubernator_tpu.ops.layout import batch_entry
from gubernator_tpu.parallel import mesh as pmesh

NOW = 1_753_700_000_000
NDEV = 8
NUM_GROUPS = 8 * NDEV  # tiny: forces group collisions -> multi-batch waves
B = 16


@pytest.mark.parametrize(
    "seed,layout",
    # fused is the factory default and what serves; wide, the
    # reference, keeps explicit differential coverage of the same SPMD
    # path (VERDICT r4 item 2).
    [(21, "fused"), (22, "fused"), (23, "fused"), (21, "wide"),
     (22, "wide")],
)
def test_sharded_mesh_fuzz(seed, layout):
    mesh = pmesh.make_mesh(jax.devices()[:NDEV])
    table = pmesh.create_sharded_table(mesh, NUM_GROUPS, ways=4, layout=layout)
    decide_fn = batch_entry(
        pmesh.make_sharded_decide(mesh, NUM_GROUPS, ways=4, layout=layout)
    )
    oracle = OracleEngine()

    rng = random.Random(seed)
    keys = [f"mf{i}" for i in range(30)]
    now = NOW

    for step in range(60):
        if rng.random() < 0.15:
            now += rng.choice([5, 500, 70_000])
        # build a wave respecting the distinct-group invariant
        reqs, used_groups = [], set()
        for _ in range(rng.randrange(1, B + 1)):
            key = rng.choice(keys)
            behavior = 0
            if rng.random() < 0.08:
                behavior |= Behavior.RESET_REMAINING
            if rng.random() < 0.12:
                behavior |= Behavior.DRAIN_OVER_LIMIT
            r = RateLimitReq(
                name="mf",
                unique_key=key,
                algorithm=rng.choice([Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]),
                behavior=behavior,
                duration=rng.choice([100, 30_000, 60_000]),
                limit=rng.choice([3, 10, 100]),
                hits=rng.choice([-1, 0, 1, 2, 5, 40]),
            )
            g = group_of(key_hash128(r.hash_key())[1], NUM_GROUPS)
            if g in used_groups:
                continue
            used_groups.add(g)
            reqs.append(r)

        b = encode_batch([dataclasses.replace(r) for r in reqs], now, NUM_GROUPS, B)
        table, out = decide_fn(table, b, now)
        for i, r in enumerate(reqs):
            want = oracle.decide(dataclasses.replace(r), now)
            got = (
                int(out.status[i]), int(out.limit[i]),
                int(out.remaining[i]), int(out.reset_time[i]),
            )
            assert got == (
                int(want.status), want.limit, want.remaining, want.reset_time
            ), f"seed {seed} step {step} item {i}: {r}"
