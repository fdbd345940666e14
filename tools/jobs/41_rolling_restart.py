"""Rolling-restart soak (ISSUE-5 acceptance): restart a 3-daemon
cluster one node at a time UNDER LOAD and assert zero counter resets
and zero failed in-flight requests with GUBER_HANDOVER on.

Procedure per node (docs/robustness.md "Rolling restarts & handover"):
decommission signal (victim ships owned state to ring successors while
still serving) -> membership flip at survivors -> drain close ->
replacement spawn -> membership flip again. Load runs continuously
through every phase; the only tolerated slack is the in-flight window —
hits applied at the victim between its handover snapshot and the
survivors' routing flip (bounded by worker concurrency, NOT by key
count: a counter RESET would lose hundreds of hits per key and trips
the per-key bound immediately).

Prints one `RESULT {json}` line like the other jobs.
"""
import os
import json
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

LIMIT = 10_000_000
N_KEYS = 120
WORKERS = 4
PER_KEY_TOLERANCE = 10  # in-flight window hits, not resets


def run() -> dict:
    import asyncio
    import random

    from gubernator_tpu.api.types import (
        PeerInfo,
        RateLimitReq,
        is_retryable_error,
    )
    from gubernator_tpu.cluster import Cluster
    from gubernator_tpu.service.config import DaemonConfig
    from gubernator_tpu.service.daemon import Daemon

    name = "rolling_soak"
    keys = [f"acct:{i}" for i in range(N_KEYS)]

    async def main():
        c = await Cluster.start(3, cache_size=65536)
        live = list(c.daemons)  # hammer targets (victim removed pre-close)
        applied = {k: 0 for k in keys}
        shed = 0
        failed = []
        running = True
        rng = random.Random(11)

        async def hammer(wid):
            nonlocal shed
            i = wid
            while running:
                k = keys[i % len(keys)]
                i += WORKERS
                d = live[rng.randrange(len(live))]
                try:
                    out = await d.svc.get_rate_limits(
                        [
                            RateLimitReq(
                                name=name, unique_key=k,
                                duration=600_000, limit=LIMIT, hits=1,
                            )
                        ]
                    )
                except Exception as e:  # transport-level failure
                    failed.append(str(e))
                    continue
                err = out[0].error
                if not err:
                    applied[k] += 1
                elif is_retryable_error(err):
                    shed += 1  # typed shed: never counted, safely redone
                else:
                    failed.append(err)
                await asyncio.sleep(0)

        async def push(daemons, membership):
            infos = [
                PeerInfo(
                    grpc_address=d.grpc_address, http_address=d.http_address
                )
                for d in membership
            ]
            tasks = []
            for d in daemons:
                d.set_peers(infos)
                t = d.svc.picker.handover_last
                if isinstance(t, asyncio.Task) and not t.done():
                    tasks.append(t)
            if tasks:
                await asyncio.wait_for(asyncio.gather(*tasks), timeout=60)

        workers = [asyncio.ensure_future(hammer(w)) for w in range(WORKERS)]
        try:
            await asyncio.sleep(2.0)  # healthy-baseline load
            restarts = 0
            for i in range(len(c.daemons)):
                victim = c.daemons[i]
                survivors = [d for d in c.daemons if d is not victim]
                live[:] = survivors
                await push([victim], survivors)  # decommission: pre-ship
                await push(survivors, survivors)  # routing flips
                await victim.close()  # graceful drain
                replacement = await Daemon.spawn(
                    DaemonConfig(
                        cache_size=65536, behaviors=victim.conf.behaviors
                    )
                )
                c.daemons[i] = replacement
                await push(c.daemons, c.daemons)  # ship the new share
                live[:] = c.daemons
                restarts += 1
                await asyncio.sleep(1.0)  # steady-state load between nodes
        finally:
            running = False
            await asyncio.gather(*workers, return_exceptions=True)

        # Verification: per-key consumed vs applied.
        probe = c.daemons[0]
        worst = 0
        regressed_total = 0
        for k in keys:
            out = await probe.svc.get_rate_limits(
                [
                    RateLimitReq(
                        name=name, unique_key=k, duration=600_000,
                        limit=LIMIT, hits=0,
                    )
                ]
            )
            consumed = LIMIT - out[0].remaining
            regress = applied[k] - consumed
            if regress > 0:
                regressed_total += regress
                worst = max(worst, regress)
        total_applied = sum(applied.values())
        ok = (
            not failed
            and worst <= PER_KEY_TOLERANCE
            and total_applied > 0
        )
        result = {
            "bench": "rolling_restart_soak",
            "daemons": 3,
            "restarts": restarts,
            "keys": N_KEYS,
            "hits_applied": total_applied,
            "hits_shed_retryable": shed,
            "failed_requests": len(failed),
            "failed_sample": failed[:3],
            "regressed_hits_total": regressed_total,
            "regressed_hits_worst_key": worst,
            "per_key_tolerance": PER_KEY_TOLERANCE,
            "handover_keys_sent": int(
                sum(
                    d.svc.metrics.handover_keys_sent.labels().get()
                    for d in c.daemons
                )
            ),
            "zero_loss_ok": ok,
        }
        await c.stop()
        return result

    return asyncio.run(main())


r = run()
print("RESULT " + json.dumps(r))
sys.exit(0 if r.get("zero_loss_ok") else 1)
