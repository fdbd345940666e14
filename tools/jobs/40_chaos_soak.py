"""Chaos soak (staged for the cluster harness): the ISSUE-3 acceptance
criterion as a measured job. With one of three daemons hard-killed
under sustained mixed (forwarded + GLOBAL) traffic, p99 latency for
keys owned by SURVIVING peers must stay within 2x the healthy baseline
— the breaker sheds the dead peer after <= threshold failures instead
of burning 5 serial timeouts per request — and aggregated GLOBAL hit
totals must reconcile across a fault-injected transient partition.

Prints one `RESULT {json}` line like the other jobs.
"""
import os
import sys, json, time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def percentile(xs, q):
    xs = sorted(xs)
    if not xs:
        return 0.0
    i = min(len(xs) - 1, int(q * len(xs)))
    return xs[i]


def run() -> dict:
    import asyncio

    from gubernator_tpu.api.types import Behavior, RateLimitReq
    from gubernator_tpu.cluster import Cluster
    from gubernator_tpu.service import pb
    from gubernator_tpu.service.config import BehaviorConfig
    from gubernator_tpu.utils import faults

    async def main():
        c = await Cluster.start(
            3,
            behaviors=BehaviorConfig(
                global_sync_wait_s=0.05,
                circuit_failure_threshold=3,
                circuit_open_base_s=0.2,
                circuit_open_max_s=1.0,
            ),
            cache_size=65536,
        )
        try:
            name = "chaos_soak"
            victim = c.find_owning_daemon(name, "victimkey")
            survivors = [d for d in c.daemons if d is not victim]
            driver = survivors[0]

            # Key sets by owner: victim-owned (the dark fault domain)
            # and survivor-owned (must stay within SLO).
            victim_keys, surv_keys = [], []
            for i in range(4000):
                k = f"k{i}"
                owner = c.find_owning_daemon(name, k)
                if owner is victim and len(victim_keys) < 200:
                    victim_keys.append(k)
                elif owner is not victim and owner is not driver and len(surv_keys) < 200:
                    surv_keys.append(k)
                if len(victim_keys) >= 200 and len(surv_keys) >= 200:
                    break

            stub = driver.client()

            async def drive(keys, n, behavior, lat_sink):
                for j in range(n):
                    msg = pb.pb.GetRateLimitsReq()
                    msg.requests.append(
                        pb.pb.RateLimitReq(
                            name=name, unique_key=keys[j % len(keys)],
                            duration=600_000, limit=10_000_000, hits=1,
                            behavior=int(behavior),
                        )
                    )
                    t0 = time.perf_counter()
                    await stub.get_rate_limits(msg, timeout=10)
                    lat_sink.append(time.perf_counter() - t0)

            # Healthy baseline: mixed forwarded + GLOBAL traffic.
            base_lat = []
            await drive(surv_keys, 400, 0, base_lat)
            await drive(surv_keys, 400, Behavior.GLOBAL, base_lat)
            base_p99 = percentile(base_lat, 0.99)

            # Hard-kill the victim (listeners die; no ring dereg).
            await victim.close()

            # Sustained mixed traffic: victim-owned keys error/degrade,
            # survivor-owned keys must stay within 2x baseline p99.
            surv_lat, victim_lat = [], []
            t_end = time.monotonic() + 20.0
            while time.monotonic() < t_end:
                await drive(surv_keys, 50, 0, surv_lat)
                await drive(surv_keys, 50, Behavior.GLOBAL, surv_lat)
                for k in victim_keys[:10]:
                    msg = pb.pb.GetRateLimitsReq()
                    msg.requests.append(
                        pb.pb.RateLimitReq(
                            name=name, unique_key=k, duration=600_000,
                            limit=10_000_000, hits=1,
                        )
                    )
                    t0 = time.perf_counter()
                    await stub.get_rate_limits(msg, timeout=10)
                    victim_lat.append(time.perf_counter() - t0)
            surv_p99 = percentile(surv_lat, 0.99)
            shed_p99 = percentile(victim_lat, 0.99)

            # GLOBAL reconciliation under a fault-injected transient
            # partition between the two survivors.
            other = survivors[1]
            gkey = next(
                k for k in surv_keys
                if c.find_owning_daemon(name, k) is other
            )
            sent = 0
            faults.INJECTOR.partition(other.grpc_address)
            for _ in range(50):
                msg = pb.pb.GetRateLimitsReq()
                msg.requests.append(
                    pb.pb.RateLimitReq(
                        name=name, unique_key=gkey, duration=600_000,
                        limit=10_000_000, hits=2,
                        behavior=int(Behavior.GLOBAL),
                    )
                )
                await stub.get_rate_limits(msg, timeout=10)
                sent += 2
            faults.INJECTOR.clear()
            deadline = time.monotonic() + 15
            reconciled = False
            while time.monotonic() < deadline:
                msg = pb.pb.GetRateLimitsReq()
                msg.requests.append(
                    pb.pb.RateLimitReq(
                        name=name, unique_key=gkey, duration=600_000,
                        limit=10_000_000, hits=0,
                        behavior=int(Behavior.GLOBAL),
                    )
                )
                resp = (await other.client().get_rate_limits(msg, timeout=10)).responses[0]
                if 10_000_000 - resp.remaining >= sent:
                    reconciled = True
                    break
                await asyncio.sleep(0.2)

            return {
                "bench": "chaos_soak",
                "daemons": 3,
                "baseline_p99_ms": round(base_p99 * 1e3, 3),
                "survivor_p99_ms": round(surv_p99 * 1e3, 3),
                "survivor_within_2x": surv_p99 <= 2 * base_p99,
                "victim_shed_p99_ms": round(shed_p99 * 1e3, 3),
                "global_hits_reconciled": reconciled,
                "requests": len(base_lat) + len(surv_lat) + len(victim_lat),
            }
        finally:
            faults.INJECTOR.clear()
            await c.stop()

    return asyncio.run(main())


r = run()
print("RESULT " + json.dumps(r))
