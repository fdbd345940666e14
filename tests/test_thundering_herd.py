"""Thundering herd: many concurrent clients hammering ONE key through a
real cluster must lose zero updates (the reference's 100-way
BenchmarkServer shape as an exactness test)."""

import asyncio

import pytest

from gubernator_tpu.api.types import RateLimitReq, Status
from gubernator_tpu.client import GubernatorClient
from gubernator_tpu.cluster import Cluster

LIMIT = 1_000_000


def start_warm(loop_thread, n):
    """An n-daemon cluster whose engines have every program compiled.
    The daemons, their warm-up threads and the herd's clients share this
    one interpreter: a herd that starts while three ladders of widths
    still compile in the background waits behind them, and a forward
    then misses the peer call's deadline (batch_timeout_s, 0.5 s) and is
    retried after the owner has applied it. A deployment closes the same
    gap with GUBER_PREWARM_BUCKETS; no deadline is changed here."""
    c = loop_thread.run(Cluster.start(n, cache_size=4096), timeout=120)
    for d in c.daemons:
        assert d.engine.wait_warm(120)
    return c


def test_thundering_herd_exact_consumption(loop_thread):
    c = start_warm(loop_thread, 3)

    async def run():
        clients = [GubernatorClient(d.grpc_address) for d in c.daemons]
        try:
            per_client_calls, hits_per_call = 5, 7
            n_tasks = 60  # 60 concurrent "clients" spread over 3 daemons

            async def hammer(i):
                cl = clients[i % len(clients)]
                for _ in range(per_client_calls):
                    out = await cl.get_rate_limits(
                        [
                            RateLimitReq(
                                name="herd", unique_key="one", duration=600_000,
                                limit=LIMIT, hits=hits_per_call,
                            )
                        ]
                    )
                    assert out[0].error == ""
                    assert out[0].status == Status.UNDER_LIMIT

            await asyncio.gather(*(hammer(i) for i in range(n_tasks)))

            # exact total: no lost updates, no double counts
            out = await clients[0].get_rate_limits(
                [
                    RateLimitReq(
                        name="herd", unique_key="one", duration=600_000,
                        limit=LIMIT, hits=0,
                    )
                ]
            )
            return out[0].remaining
        finally:
            for cl in clients:
                await cl.close()

    try:
        remaining = loop_thread.run(run(), timeout=120)
        assert remaining == LIMIT - 60 * 5 * 7
    finally:
        loop_thread.run(c.stop())


def test_thundering_herd_global_exact_replication(loop_thread):
    """GLOBAL herd through the columnar fast edge: many concurrent
    batches from every daemon, replication legs hopping from the serving
    executor to each daemon's loop — the owner's authoritative counter
    must converge to the EXACT total (no lost or double-queued hits),
    and every replica must agree."""
    import time as _time

    from gubernator_tpu.api.types import Behavior

    c = start_warm(loop_thread, 3)

    async def run():
        clients = [GubernatorClient(d.grpc_address) for d in c.daemons]
        try:
            per_client_calls, hits_per_call, n_tasks = 5, 3, 30
            keys = [f"gh{j}" for j in range(8)]

            async def hammer(i):
                cl = clients[i % len(clients)]
                for _ in range(per_client_calls):
                    out = await cl.get_rate_limits(
                        [
                            RateLimitReq(
                                name="gherd", unique_key=k,
                                duration=600_000, limit=LIMIT,
                                hits=hits_per_call,
                                behavior=Behavior.GLOBAL,
                            )
                            for k in keys
                        ]
                    )
                    for r in out:
                        assert r.error == ""

            await asyncio.gather(*(hammer(i) for i in range(n_tasks)))

            want = LIMIT - n_tasks * per_client_calls * hits_per_call
            deadline = _time.monotonic() + 15
            got = {}
            while _time.monotonic() < deadline:
                got = {}
                for cl in clients:  # every replica must agree
                    out = await cl.get_rate_limits(
                        [
                            RateLimitReq(
                                name="gherd", unique_key=k,
                                duration=600_000, limit=LIMIT, hits=0,
                                behavior=Behavior.GLOBAL,
                            )
                            for k in keys
                        ]
                    )
                    for k, r in zip(keys, out):
                        got.setdefault(k, set()).add(r.remaining)
                if all(v == {want} for v in got.values()):
                    return got
                await asyncio.sleep(0.2)
            return got
        finally:
            for cl in clients:
                await cl.close()

    try:
        got = loop_thread.run(run(), timeout=180)
        want = LIMIT - 30 * 5 * 3
        assert all(v == {want} for v in got.values()), (got, want)
    finally:
        loop_thread.run(c.stop())
