"""Cooperative-lease soak (docs/architecture.md "Cooperative leases"):
the same Zipf-skewed single-check trace against a 3-daemon mesh twice —
(a) a plain client, every check a gRPC round trip (plus peer forwarding
inside the mesh), and (b) a lease-holding client that answers checks
from locally held slices and reconciles through batched Lease RPCs.

Acceptance evidence (ISSUE 13): `rpc_reduction` (mesh RPCs per check,
baseline / leased) >= 10 with `p99_ratio` (leased p99 / baseline p99)
no worse than 1, and the partition drill — the holder vanishes without
returning its slices, the fleet-wide over-admission stays bounded by
the outstanding ledger, and the expiry sweep drives
`gubernator_lease_outstanding_hits` back to 0 (`healed`).

Prints one `RESULT {json}` line.
"""
import os
import re, sys, json, time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def run() -> dict:
    import asyncio

    import numpy as np

    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.client import GubernatorClient
    from gubernator_tpu.cluster import Cluster
    from gubernator_tpu.service.config import BehaviorConfig

    N_KEYS = 64
    CHECKS = 2_000  # single-request calls per phase
    LIMIT = 1_000_000
    TTL_S, SWEEP_S = 5.0, 0.5

    # Zipf-weighted ranks: the hot head is leased once and served
    # locally thousands of times; the tail exercises grant churn.
    rng = np.random.default_rng(37)
    w = 1.0 / np.arange(1, N_KEYS + 1, dtype=np.float64) ** 1.1
    w /= w.sum()
    trace = rng.choice(N_KEYS, size=CHECKS, p=w)

    def req(i: int) -> RateLimitReq:
        return RateLimitReq(
            name="lease_soak", unique_key=f"acct:{i}",
            duration=600_000, limit=LIMIT, hits=1,
        )

    async def main():
        c = await Cluster.start(
            3,
            behaviors=BehaviorConfig(
                leases=True, lease_ttl_s=TTL_S, lease_fraction=0.1,
                lease_sweep_interval_s=SWEEP_S, retry_after=True,
            ),
            cache_size=65536,
        )
        try:
            def mesh_rpcs() -> int:
                # Every gRPC the mesh served, client-facing AND
                # peer-to-peer (forwarding, Lease, broadcasts) — the
                # honest denominator for "RPCs per check".
                total = 0
                for d in c.daemons:
                    text = d.svc.metrics.render().decode()
                    for m in re.finditer(
                        r'gubernator_grpc_request_duration_count'
                        r'\{method="[^"]+"\} ([0-9.e+]+)',
                        text,
                    ):
                        total += int(float(m.group(1)))
                return total

            def outstanding() -> int:
                return sum(
                    d.svc.lease_mgr.outstanding_hits() for d in c.daemons
                )

            async def drive(client: GubernatorClient) -> dict:
                lat = []
                peak_out = 0
                r0 = mesh_rpcs()
                t0 = time.perf_counter()
                for n, k in enumerate(trace):
                    s = time.perf_counter()
                    (resp,) = await client.get_rate_limits(
                        [req(int(k))], timeout=10
                    )
                    assert resp.error == "", resp.error
                    lat.append(time.perf_counter() - s)
                    if n % 100 == 0:
                        peak_out = max(peak_out, outstanding())
                dt = time.perf_counter() - t0
                # Let in-flight lease maintenance land before counting.
                await asyncio.sleep(0.2)
                return {
                    "throughput": CHECKS / dt,
                    "p50_ms": float(np.percentile(lat, 50) * 1e3),
                    "p99_ms": float(np.percentile(lat, 99) * 1e3),
                    "rpcs": mesh_rpcs() - r0,
                    "peak_outstanding_hits": peak_out,
                }

            addr = c.daemons[0].grpc_address

            base_client = GubernatorClient(addr)
            try:
                baseline = await drive(base_client)
            finally:
                await base_client.close()

            lease_client = GubernatorClient(
                addr, leases=True, lease_max_keys=4096
            )
            # Warm: one pass over the keyspace so the hot head's slices
            # are held before the measured phase.
            for i in range(N_KEYS):
                await lease_client.get_rate_limits([req(i)], timeout=10)
            for _ in range(100):
                if lease_client.lease_cache._entries:
                    break
                await asyncio.sleep(0.05)
            leased = await drive(lease_client)
            cache_stats = lease_client.lease_cache.summary()

            # Partition drill: the holder vanishes WITHOUT returning its
            # slices (drop the cache so close() has nothing to return).
            abandoned = outstanding()
            lease_client.lease_cache = None
            await lease_client.close()
            t0 = time.perf_counter()
            healed_s = None
            while time.perf_counter() - t0 < TTL_S + 10 * SWEEP_S + 10.0:
                if outstanding() == 0:
                    healed_s = time.perf_counter() - t0
                    break
                await asyncio.sleep(SWEEP_S / 2)

            # Conservation after the dust settles: every owner's ledger
            # must balance and match its per-record view.
            ledgers = []
            conserved = True
            for d in c.daemons:
                lm = d.svc.lease_mgr
                s = lm.summary()
                by_key = sum(lm.outstanding_by_key().values())
                ok = (
                    s["granted_hits"] - s["returned_hits"]
                    - s["expired_hits"] == s["outstanding_hits"]
                    and by_key == s["outstanding_hits"]
                )
                conserved = conserved and ok
                ledgers.append(
                    {
                        "address": d.grpc_address,
                        "granted_hits": s["granted_hits"],
                        "returned_hits": s["returned_hits"],
                        "expired_hits": s["expired_hits"],
                        "outstanding_hits": s["outstanding_hits"],
                        "revocations": s["revocations"],
                    }
                )

            rpc_reduction = baseline["rpcs"] / max(1, leased["rpcs"])
            p99_ratio = (
                leased["p99_ms"] / baseline["p99_ms"]
                if baseline["p99_ms"] else None
            )
            return {
                "bench": "lease_soak",
                "metric": (
                    "leased Zipf serving (3-daemon mesh, "
                    f"{N_KEYS} keys) checks/s"
                ),
                "value": round(leased["throughput"], 1),
                "unit": "checks/s",
                "daemons": 3,
                "keys": N_KEYS,
                "checks": CHECKS,
                "baseline": {
                    k: round(v, 3) for k, v in baseline.items()
                },
                "leased": {k: round(v, 3) for k, v in leased.items()},
                "cache": cache_stats,
                "rpc_reduction": round(rpc_reduction, 2),
                "rpc_reduction_10x": bool(rpc_reduction >= 10.0),
                "p99_ratio": round(p99_ratio, 3) if p99_ratio else None,
                "abandoned_outstanding_hits": abandoned,
                # One holder: at most one active slice per key plus one
                # renewal-overlap slice — the fleet can never over-admit
                # past this however the partition falls.
                "over_admission_bounded": bool(
                    leased["peak_outstanding_hits"]
                    <= 2 * N_KEYS * (LIMIT // 10)
                ),
                "healed_after_abandon_s": (
                    round(healed_s, 2) if healed_s is not None else None
                ),
                "healed": bool(healed_s is not None),
                "ledgers_conserved": bool(conserved),
                "ledgers": ledgers,
            }
        finally:
            await c.stop()

    return asyncio.run(main())


r = run()
print("RESULT " + json.dumps(r))
