"""Admission-observatory soak (docs/monitoring.md "Admission"): measured
fleet enforcement error under chaos — partition + leases + paged table
all on, per ISSUE 14.

A 3-daemon mesh (every table paged: 4 pages, budget 3, so the cold tier
is live) serves one keyspace owned by a single daemon. The drill:

1. lease warm — a lease client carves slices for every key (the
   outstanding-hits half of the published over-admission bound);
2. saturate — drain every key to remaining=0 at the owner, so the
   owner-local table records admitted == limit exactly;
3. partition — fault-inject the owner's address; the edge daemon's
   breaker opens and degraded-local answers admit EXTRA hits from its
   own table while queueing them for reconciliation. The measured fleet
   over-admission (Σ per-daemon admission-scan admitted_hits minus the
   configured fleet limit) must stay within the bound the fleet itself
   publishes: Σ /debug/admission `bound.total_hits` (lease outstanding
   + GLOBAL in-flight hits);
4. heal — clear the fault, abandon the lease holder. Queued hits drain,
   leases expire via the sweep, the degraded windows elapse — measured
   fleet excess must return to exactly 0.

Acceptance evidence (ISSUE 14): `partition.within_bound`,
`healed.excess_zero`, `healed.bound_zero`. Prints one `RESULT {json}`
line.
"""
import os
import sys, json, time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def run() -> dict:
    import asyncio

    import jax

    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.client import GubernatorClient
    from gubernator_tpu.cluster import Cluster
    from gubernator_tpu.service.config import BehaviorConfig, DaemonConfig
    from gubernator_tpu.service.daemon import Daemon
    from gubernator_tpu.utils import faults

    N_KEYS = 48
    LIMIT = 200
    DURATION_MS = 30_000  # windows must outlive phases 1-3, expire in 4
    LEASE_TTL_S, SWEEP_S = 20.0, 0.5
    CHUNK, ROUNDS = 10, 6  # partition-phase extra hits: 60 per key

    def req(i: int, hits: int) -> RateLimitReq:
        return RateLimitReq(
            name="admission_soak", unique_key=f"acct:{i}",
            duration=DURATION_MS, limit=LIMIT, hits=hits,
        )

    async def main():
        behaviors = BehaviorConfig(
            leases=True, lease_ttl_s=LEASE_TTL_S, lease_fraction=0.1,
            lease_sweep_interval_s=SWEEP_S, retry_after=True,
            owner_unreachable="local",
            circuit_failure_threshold=3,
            circuit_open_base_s=0.2, circuit_open_max_s=2.0,
            global_sync_wait_s=0.1,
        )
        # Cluster.start doesn't expose table knobs; assemble by hand so
        # every daemon runs the PAGED table (4 pages, 3 resident) with
        # provenance metadata on and a fast admission-scan TTL.
        c = Cluster()
        for _ in range(3):
            c.daemons.append(
                await Daemon.spawn(
                    DaemonConfig(
                        cache_size=8192,
                        behaviors=behaviors,
                        page_groups=256, page_budget=3,
                        admission_ttl_s=0.5,
                        stage_metadata=True,
                    )
                )
            )
        c.rewire()
        try:
            owner = c.find_owning_daemon("admission_soak", "acct:0")
            edge = next(d for d in c.daemons if d is not owner)
            keys = [
                i for i in range(4000)
                if c.find_owning_daemon("admission_soak", f"acct:{i}")
                is owner
            ][:N_KEYS]
            assert len(keys) == N_KEYS
            fleet_limit = N_KEYS * LIMIT

            def fleet() -> dict:
                # Force-fresh scans (max_age_s=0) so the phase
                # transition is visible; production scrapes ride the
                # TTL cache instead.
                admitted = bound = 0
                per = []
                for d in c.daemons:
                    snap = d.svc.engine.admission_snapshot(max_age_s=0)
                    blob = d.svc.admission_debug_info(include_ring=False)
                    admitted += int(snap["admitted_hits"])
                    bound += int(blob["bound"]["total_hits"])
                    per.append(
                        {
                            "admitted_hits": int(snap["admitted_hits"]),
                            "limit_hits": int(snap["limit_hits"]),
                            "keys": int(snap["keys"]),
                            "bound_hits": int(blob["bound"]["total_hits"]),
                        }
                    )
                excess = max(0, admitted - fleet_limit)
                return {
                    "fleet_admitted_hits": admitted,
                    "fleet_limit_hits": fleet_limit,
                    "excess_hits": excess,
                    "excess_ratio": round(excess / fleet_limit, 4),
                    "bound_hits": bound,
                    "daemons": per,
                }

            addr = edge.grpc_address

            # -- 1. lease warm: carve a slice per key ------------------
            lease_client = GubernatorClient(
                addr, leases=True, lease_max_keys=4096
            )
            for i in keys:
                (resp,) = await lease_client.get_rate_limits(
                    [req(i, 1)], timeout=10
                )
                assert resp.error == "", resp.error

            # -- 2. saturate the owner to admitted == limit ------------
            plain = GubernatorClient(addr)
            for i in keys:
                (probe,) = await plain.get_rate_limits(
                    [req(i, 0)], timeout=10
                )
                assert probe.error == "", probe.error
                if probe.remaining > 0:
                    (resp,) = await plain.get_rate_limits(
                        [req(i, int(probe.remaining))], timeout=10
                    )
                    assert resp.error == "", resp.error
            steady = fleet()

            # -- 3. partition the owner; degraded-local over-admits ----
            faults.INJECTOR.partition(owner.grpc_address)
            served = errors = 0
            lat = []
            t0 = time.perf_counter()
            for _ in range(ROUNDS):
                for i in keys:
                    s = time.perf_counter()
                    (resp,) = await plain.get_rate_limits(
                        [req(i, CHUNK)], timeout=10
                    )
                    lat.append(time.perf_counter() - s)
                    if resp.error:
                        errors += 1  # breaker still warming
                    else:
                        served += 1
            dt = time.perf_counter() - t0
            # A few lease-local debits ride along (zero RPC, zero table
            # churn — client-side slices were charged at grant time).
            for i in keys[:8]:
                await lease_client.get_rate_limits([req(i, 1)], timeout=10)
            partition = fleet()
            partition["degraded_checks_per_s"] = round(
                (served + errors) / dt, 1
            )
            partition["served"] = served
            partition["errors"] = errors
            partition["within_bound"] = bool(
                partition["excess_hits"] <= partition["bound_hits"]
            )
            # Decision mix at the edge: provenance counters, no ring.
            partition["edge_decisions"] = edge.svc.admission_debug_info(
                include_ring=False
            )["decisions"]
            audit_partition = None
            if owner._auditor is not None:
                await owner._auditor.audit_once()
                audit_partition = owner._auditor.summary().get("admission")

            # -- 4. heal: clear fault, abandon the lease holder --------
            faults.INJECTOR.clear()
            lease_client.lease_cache = None  # vanish without returning
            await lease_client.close()
            t0 = time.perf_counter()
            healed = None
            deadline = DURATION_MS / 1e3 + LEASE_TTL_S + 60.0
            while time.perf_counter() - t0 < deadline:
                f = fleet()
                if f["excess_hits"] == 0 and f["bound_hits"] == 0:
                    healed = f
                    healed["healed_s"] = round(time.perf_counter() - t0, 2)
                    break
                await asyncio.sleep(1.0)
            await plain.close()
            if healed is None:
                healed = fleet()
                healed["healed_s"] = None
            healed["excess_zero"] = healed["excess_hits"] == 0
            healed["bound_zero"] = healed["bound_hits"] == 0

            lat.sort()
            p99_ms = lat[min(len(lat) - 1, int(0.99 * len(lat)))] * 1e3
            return {
                "bench": "admission_soak",
                "metric": (
                    "degraded-partition admission soak "
                    f"({jax.default_backend()}, 3-daemon paged mesh, "
                    f"{N_KEYS} keys) checks/s"
                ),
                "value": partition["degraded_checks_per_s"],
                "unit": "checks/s",
                "daemons": 3,
                "keys": N_KEYS,
                "limit": LIMIT,
                "duration_ms": DURATION_MS,
                "partition_p99_ms": round(p99_ms, 3),
                "steady": steady,
                "partition": partition,
                "healed": healed,
                "auditor_admission": audit_partition,
                "within_bound": partition["within_bound"],
                "excess_measured": partition["excess_hits"] > 0,
                "healed_to_zero": bool(
                    healed["excess_zero"] and healed["bound_zero"]
                ),
            }
        finally:
            faults.INJECTOR.clear()
            await c.stop()

    return asyncio.run(main())


r = run()
print("RESULT " + json.dumps(r))
