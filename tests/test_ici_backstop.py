"""ICI capped-tick fingerprint-collision backstop (GUBER_ICI_FULL_TICK_EVERY).

The capped sync tick selects groups to merge by comparing two salted
non-cryptographic content fingerprints across devices. On a collision a
diverged group reads as converged and is stranded forever — the merge
never runs for it. The backstop forces one full-table tick every N
capped ticks, bounding the stranded window to N * sync_wait_s.

The collision is forged by monkeypatching the fingerprint mixer (the
fused layout's own, ops/fused.py _mix32) to a constant BEFORE the sync
programs trace, making the selector fingerprint-blind; divergence is
then planted with zero pending deltas (the only signal the blinded
selector has left).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gubernator_tpu.api.types import Behavior, RateLimitReq
from gubernator_tpu.ops.encode import encode_batch
from gubernator_tpu.ops import fused
from gubernator_tpu.ops.layout import batch_entry
from gubernator_tpu.parallel import ici
from gubernator_tpu.parallel import mesh as pmesh
from gubernator_tpu.runtime.ici_engine import IciEngine, IciEngineConfig

NOW = 1_753_700_000_000
NDEV = 4


def _tables_equal_across_devices(state) -> bool:
    for leaf in jax.tree_util.tree_leaves(state.table):
        a = np.asarray(leaf)
        for d in range(1, a.shape[0]):
            if not np.array_equal(a[0], a[d]):
                return False
    return True


def test_forged_collision_strands_capped_tick_and_full_tick_heals(monkeypatch):
    # Blind the selector: every salted fingerprint becomes the constant
    # 0 on every device, so content divergence can never be detected.
    # Must land before make_sync_step traces (the mixer is baked in at
    # trace). The serving layout fingerprints its groups itself
    # (ops/fused.py), so the mixer to blind is its own.
    monkeypatch.setattr(fused, "_mix32", jnp.zeros_like)
    mesh = pmesh.make_mesh(jax.devices()[:NDEV])
    num_slots, ways = 64, 2
    num_groups = num_slots // ways
    state = ici.create_ici_state(mesh, num_slots, ways)
    replica_fn = batch_entry(ici.make_replica_decide(mesh, num_slots, ways))
    capped_fn = ici.make_sync_step(mesh, num_slots, ways, max_sync_groups=2)
    full_fn = ici.make_sync_step(mesh, num_slots, ways, max_sync_groups=None)

    req = RateLimitReq(
        name="bs", unique_key="k", behavior=Behavior.GLOBAL,
        duration=600_000, limit=100, hits=1,
    )
    batch = encode_batch([dataclasses.replace(req)], NOW, num_groups, 2)
    state, _ = replica_fn(state, batch, np.zeros((2,), dtype=np.int64), NOW)
    state, _ = full_fn(state, NOW)
    assert _tables_equal_across_devices(state)

    # Plant the stranded divergence: a hit applied on device 1 only,
    # then its pending delta erased — exactly what a fingerprint
    # collision leaves behind (content differs, nothing else signals).
    batch = encode_batch([dataclasses.replace(req)], NOW, num_groups, 2)
    state, _ = replica_fn(state, batch, np.ones((2,), dtype=np.int64), NOW)
    zero_pend = jax.device_put(
        jnp.zeros_like(state.pending), state.pending.sharding
    )
    state = state._replace(pending=zero_pend)
    assert not _tables_equal_across_devices(state)

    # Capped ticks are fingerprint-blind: the diverged group is never
    # selected (0 groups merged) and the tables stay diverged.
    for i in range(5):
        state, diag = capped_fn(state, NOW + 1 + i)
        assert int(np.asarray(diag)[:, 3].max()) == 0
    assert not _tables_equal_across_devices(state)

    # One full-table tick heals regardless of fingerprints.
    state, _ = full_fn(state, NOW + 10)
    assert _tables_equal_across_devices(state)


def test_engine_forces_full_tick_every_n_and_counts():
    cfg = IciEngineConfig(
        devices=jax.devices()[:NDEV],
        num_groups=64,
        ways=2,
        num_slots=128,
        replica_ways=2,
        batch_size=16,
        sync_wait_s=3600,  # manual ticks via sync_now()
        max_sync_groups=4,  # capped: 4 < 128/2 replica groups
        full_tick_every=3,
    )
    eng = IciEngine(cfg)
    try:
        assert eng._rtier.sync_full is not None
        assert eng.full_ticks == 0
        for _ in range(3):
            eng.sync_now()
        assert eng.full_ticks == 1
        for _ in range(3):
            eng.sync_now()
        assert eng.full_ticks == 2

        # The counter reaches /metrics through the engine_sync bridge.
        from gubernator_tpu.metrics import Metrics, wire_engine_telemetry

        m = Metrics()
        wire_engine_telemetry(m, eng)
        text = m.render().decode()
        assert "gubernator_ici_full_ticks 2" in text
    finally:
        eng.close()


def test_engine_skips_backstop_when_uncapped():
    # A cap >= the replica group count compiles to the uncapped program;
    # building (and warming) a redundant second program would be waste.
    cfg = IciEngineConfig(
        devices=jax.devices()[:NDEV],
        num_groups=64,
        ways=2,
        num_slots=128,
        replica_ways=2,
        batch_size=16,
        sync_wait_s=3600,
        max_sync_groups=None,
        full_tick_every=3,
    )
    eng = IciEngine(cfg)
    try:
        assert eng._rtier.sync_full is None
        eng.sync_now()
        assert eng.full_ticks == 0
    finally:
        eng.close()
