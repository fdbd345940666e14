"""Rolling-restart elasticity (chaos marker — tier-1 covers it):
restart a 3-daemon cluster one node at a time and assert ZERO counter
loss — every hit applied before and between restarts is still reflected
in each key's remaining afterwards.

The restart procedure mirrors docs/robustness.md "Rolling restarts &
handover": decommission signal to the victim (it ships owned state to
ring successors while still serving), membership flip at the survivors,
drain close, replacement spawn, membership flip again (survivors ship
the replacement's share). Load pauses during the flips, so the
assertion is exact equality, not a tolerance band."""

import asyncio
import random

import pytest

from gubernator_tpu.api.types import PeerInfo, RateLimitReq
from gubernator_tpu.cluster import Cluster
from gubernator_tpu.service.config import DaemonConfig
from gubernator_tpu.service.daemon import Daemon

pytestmark = pytest.mark.chaos

NAME = "rolling"
LIMIT = 10_000
KEYS = [f"acct:{i}" for i in range(40)]


async def _apply_round(c, sent, rng):
    """One hit per key via a random daemon; every call must succeed."""
    for k in KEYS:
        d = c.daemons[rng.randrange(len(c.daemons))]
        out = await d.svc.get_rate_limits(
            [
                RateLimitReq(
                    name=NAME, unique_key=k, duration=600_000,
                    limit=LIMIT, hits=1,
                )
            ]
        )
        assert out[0].error == "", out[0].error
        sent[k] += 1


async def _push(daemons, membership):
    """Swap membership on `daemons` and await the handovers it spawns."""
    infos = [
        PeerInfo(grpc_address=d.grpc_address, http_address=d.http_address)
        for d in membership
    ]
    tasks = []
    for d in daemons:
        d.set_peers(infos)
        t = d.svc.picker.handover_last
        if isinstance(t, asyncio.Task) and not t.done():
            tasks.append(t)
    if tasks:
        await asyncio.wait_for(asyncio.gather(*tasks), timeout=30)


async def _verify(c, sent):
    probe = c.daemons[0]
    for k in KEYS:
        out = await probe.svc.get_rate_limits(
            [
                RateLimitReq(
                    name=NAME, unique_key=k, duration=600_000,
                    limit=LIMIT, hits=0,
                )
            ]
        )
        assert out[0].error == "", out[0].error
        assert out[0].remaining == LIMIT - sent[k], (
            f"counter for {k!r} regressed: remaining={out[0].remaining}, "
            f"expected {LIMIT - sent[k]} after {sent[k]} hit(s)"
        )


def test_rolling_restart_zero_counter_loss(loop_thread):
    async def main():
        rng = random.Random(7)
        c = await Cluster.start(3, cache_size=8192)
        try:
            sent = {k: 0 for k in KEYS}
            await _apply_round(c, sent, rng)
            for i in range(len(c.daemons)):
                victim = c.daemons[i]
                survivors = [d for d in c.daemons if d is not victim]
                # 1. Decommission signal: the victim ships its owned
                #    keys to ring successors while still serving.
                await _push([victim], survivors)
                # 2. Survivors flip routing to the pre-warmed successors.
                await _push(survivors, survivors)
                # 3. Drain close: queues flush, residual state re-ships.
                await victim.close()
                # 4. Replacement joins; survivors ship its ring share.
                replacement = await Daemon.spawn(
                    DaemonConfig(
                        cache_size=8192, behaviors=victim.conf.behaviors
                    )
                )
                c.daemons[i] = replacement
                await _push(c.daemons, c.daemons)
                # Load between restarts: counts must keep continuing.
                await _apply_round(c, sent, rng)
            await _verify(c, sent)
            # The handover path really ran: this node shipped keys.
            shipped = sum(
                d.svc.metrics.handover_keys_sent.labels().get()
                for d in c.daemons
            )
            assert shipped > 0
        finally:
            await c.stop()

    loop_thread.run(main(), timeout=300)
