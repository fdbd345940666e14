"""Unified mesh engine (runtime/engine.py MeshEngine + runtime/topology.py):
mesh shape (1,) IS the single-chip engine, and the (chips,) sharded tier
must be bit-exact with it — on both table layouts, flat AND paged,
through demote/promote churn, across pipeline depths, and across a
snapshot handover between a flat single-chip engine and a paged mesh
engine. The single-chip depth/bit-exactness pins live in
tests/test_pipeline.py + tests/test_kernel_fuzz.py (run UNMODIFIED by
the unification); this file pins the mesh side of the same contract.

8 XLA host-platform faked devices (tests/conftest.py)."""

import dataclasses
import random

import pytest

from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig
from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig

NOW = 1_753_700_000_000

NUM_GROUPS = 256
PAGE_GROUPS = 16  # -> 16 logical pages, 2 per shard at 8 devices


def tup(rl):
    return (rl.status, rl.limit, rl.remaining, rl.reset_time, rl.error)


def mk_flat_single(layout, clock, **kw):
    kw.setdefault("num_groups", NUM_GROUPS)
    kw.setdefault("batch_size", 32)
    kw.setdefault("batch_wait_s", 0.001)
    return DeviceEngine(
        EngineConfig(layout=layout, **kw), now_fn=lambda: clock["now"]
    )


def mk_mesh(layout, clock, *, paged=False, **kw):
    kw.setdefault("num_groups", NUM_GROUPS)
    kw.setdefault("num_slots", 2048)
    kw.setdefault("batch_size", 32)
    kw.setdefault("batch_wait_s", 0.001)
    kw.setdefault("sync_wait_s", 3600.0)  # manual ticks only
    if paged:
        kw.setdefault("page_groups", PAGE_GROUPS)
        kw.setdefault("page_budget", 16)
        kw.setdefault("page_demote_interval_s", 0)
    return IciEngine(
        IciEngineConfig(layout=layout, **kw), now_fn=lambda: clock["now"]
    )


def _fuzz_reqs(rng, n, keys):
    out = []
    for _ in range(n):
        behavior = 0
        if rng.random() < 0.08:
            behavior |= Behavior.RESET_REMAINING
        out.append(
            RateLimitReq(
                name=rng.choice(["ma", "mb"]),
                unique_key=f"acct:{rng.randrange(keys)}",
                algorithm=rng.choice(
                    [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                ),
                behavior=behavior,
                duration=rng.choice([5_000, 60_000, 600_000]),
                limit=rng.choice([1, 10, 100]),
                hits=rng.choice([0, 1, 1, 2, 5, 50]),
                burst=rng.choice([0, 0, 10]),
            )
        )
    return out


# ---------------------------------------------------------------------------
# mesh vs single-chip bit-exact parity, both layouts, flat AND paged, over
# all 8 faked devices and over 4 (a v5e host's mesh)


@pytest.mark.parametrize("n_dev", [8, 4])
@pytest.mark.parametrize("layout", ["fused", "wide"])
def test_mesh_matches_single_chip(layout, n_dev):
    """The same fuzz stream (duplicates, resets, clock jumps, both
    algorithms) through the flat single-chip engine (the oracle — mesh
    shape (1,)), the flat mesh sharded tier, and the PAGED mesh sharded
    tier: every response bit-exact, at every step."""
    import jax

    clock = {"now": NOW}
    rng = random.Random(hash(layout) & 0xFFFF)
    devices = jax.devices()[:n_dev]
    single = mk_flat_single(layout, clock)
    mesh_flat = mk_mesh(layout, clock, devices=devices)
    mesh_paged = mk_mesh(layout, clock, paged=True, devices=devices)
    try:
        for _ in range(5):
            clock["now"] += rng.choice([1, 700, 6_000])
            reqs = _fuzz_reqs(rng, rng.randrange(1, 24), keys=40)
            want = [tup(r) for r in single.check_batch(
                [dataclasses.replace(r) for r in reqs]
            )]
            got_flat = [tup(r) for r in mesh_flat.check_batch(
                [dataclasses.replace(r) for r in reqs]
            )]
            assert got_flat == want, f"flat mesh diverged ({layout} x{n_dev})"
            got_paged = [tup(r) for r in mesh_paged.check_batch(
                [dataclasses.replace(r) for r in reqs]
            )]
            assert got_paged == want, f"paged mesh diverged ({layout} x{n_dev})"
    finally:
        single.close()
        mesh_flat.close()
        mesh_paged.close()


# ---------------------------------------------------------------------------
# paged sharded tier: zero loss through demote/promote churn


def test_paged_mesh_zero_loss_through_churn():
    """Budget 8 frames = ONE resident frame per shard against 16 logical
    pages: single-key flushes force a demote+promote cycle nearly every
    time the stream hops pages within a shard. Every response must stay
    bit-exact with a flat single-chip twin (which never demotes), i.e.
    demotion to the host tier and promotion back lose NOTHING."""
    clock = {"now": NOW}
    single = mk_flat_single("fused", clock)
    paged = mk_mesh("fused", clock, paged=True, page_budget=8)
    rng = random.Random(77)
    # keys spread over the whole group space -> all 16 logical pages
    keys = [f"churn:{i}" for i in range(48)]
    try:
        for round_ in range(4):
            clock["now"] += 500
            rng.shuffle(keys)
            for k in keys:
                r = RateLimitReq(
                    name="churn", unique_key=k, duration=600_000,
                    limit=1000, hits=1,
                )
                want = tup(single.check_batch([dataclasses.replace(r)])[0])
                got = tup(paged.check_batch([dataclasses.replace(r)])[0])
                assert got == want, (round_, k)
        # churn actually happened — the budget forced real paging
        pages = paged.table_census(max_age_s=0)["pages"]
        assert pages["demotes"] > 0 and pages["promotes"] > 0, pages
        assert pages["host"] + pages["resident"] > 0
        # and nothing was lost: a zero-hit read of every key agrees
        for k in keys:
            r = RateLimitReq(
                name="churn", unique_key=k, duration=600_000,
                limit=1000, hits=0,
            )
            want = tup(single.check_batch([dataclasses.replace(r)])[0])
            got = tup(paged.check_batch([dataclasses.replace(r)])[0])
            assert got == want, k
    finally:
        single.close()
        paged.close()


# ---------------------------------------------------------------------------
# pipeline depth-equivalence on the unified core's mesh path


def test_mesh_pipeline_depth_equivalence():
    """The continuous-batching contract holds on the mesh exactly as on
    one chip (tests/test_pipeline.py): the same burst-shaped stream
    through depths 1 (serial pump), 2, and 3 produces identical
    responses. Waves here run BOTH tiers (sharded + replica GLOBAL)."""
    clock = {"now": NOW}
    rng = random.Random(5)
    streams = []
    for _ in range(4):
        reqs = _fuzz_reqs(rng, 40, keys=24)
        for i, r in enumerate(reqs):
            if i % 5 == 0:
                reqs[i] = dataclasses.replace(
                    r, behavior=r.behavior | Behavior.GLOBAL
                )
        streams.append(reqs)
    results = {}
    for depth in (1, 2, 3):
        eng = mk_mesh("fused", clock, pipeline_depth=depth)
        got = []
        try:
            for reqs in streams:
                futs = [
                    eng.check_async(dataclasses.replace(r)) for r in reqs
                ]
                got.extend(tup(f.result(timeout=60)) for f in futs)
        finally:
            eng.close()
        results[depth] = got
    assert results[1] == results[2] == results[3]


# ---------------------------------------------------------------------------
# handover interop: flat single-chip <-> paged mesh via snapshots


def test_handover_flat_single_to_paged_mesh_and_back():
    """Ownership handover across ENGINE SHAPES: counters written on a
    flat single-chip engine move via portable snapshots into a paged
    mesh engine (merge_snapshots_lww — the ring-change receiver path)
    and keep counting exactly; then the survivors move back through
    inject_snapshots (the Loader restore path) into a fresh flat
    single-chip engine. The paged mesh side must produce routable
    snapshots from a table whose rows live in per-shard frames and
    host-DRAM cold tiers."""
    from gubernator_tpu.store.store import (
        merge_snapshots_lww,
        snapshots_from_engine,
    )

    clock = {"now": NOW}
    keys = [f"ho:{i}" for i in range(24)]

    def hit(eng, k, hits, limit=1000):
        return eng.check_batch(
            [RateLimitReq(
                name="ho", unique_key=k, duration=600_000,
                limit=limit, hits=hits,
            )]
        )[0]

    flat = mk_flat_single("fused", clock)
    paged = mk_mesh("fused", clock, paged=True)
    try:
        for i, k in enumerate(keys):
            hit(flat, k, 3 + (i % 4))
        snaps = snapshots_from_engine(flat)
        assert len(snaps) == len(keys)
        accepted, stale = merge_snapshots_lww(paged, snaps)
        assert (accepted, stale) == (len(keys), 0)
        # the new owner continues the SAME counters
        for i, k in enumerate(keys):
            got = hit(paged, k, 1)
            assert got.remaining == 1000 - (3 + (i % 4)) - 1, k

        # ... and hands them back: paged-mesh snapshots restore into a
        # fresh flat single-chip engine (Loader path), counts intact.
        back = snapshots_from_engine(paged)
        assert {s.key for s in back} == {f"ho_{k}" for k in keys}
        flat2 = mk_flat_single("fused", clock)
        try:
            flat2.inject_snapshots(back)
            for i, k in enumerate(keys):
                got = hit(flat2, k, 0)
                assert got.remaining == 1000 - (3 + (i % 4)) - 1, k
        finally:
            flat2.close()
    finally:
        flat.close()
        paged.close()
