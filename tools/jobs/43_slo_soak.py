"""SLO-observatory soak (docs/monitoring.md "SLOs & burn rates"): drive
the admission-accuracy SLO through a full burn-rate alert cycle with a
real fault, per ISSUE 17.

A 3-daemon mesh serves one GLOBAL keyspace owned by a single daemon,
with the observatory sampling fast (0.25s) and the admission-accuracy
SLO's windows shrunk via the GUBER_SLO_SPECS merge override so the
whole multi-window story fits in seconds instead of hours. The
admission-accuracy SLI is the node's unreconciled admission debt —
lease outstanding + GLOBAL in-flight hits, the published
over-admission bound — as a fraction of the capacity admitted this
window. The drill:

1. steady — warm traffic flushes clean: debt 0, SLO "ok" with the full
   error budget (provably healthy, not data-less);
2. partition — fault-inject the owner's address, then pump the window
   limit through an edge. GLOBAL answers locally and queues every hit
   for the owner; the flush can't deliver, the breaker opens, and the
   debt pins near 1.0 of windowed capacity. The edge's
   admission-accuracy SLO must reach `fast_burn` within ONE evaluation
   window (the long fast window) of the first bad sample — observed
   end-to-end through /debug/slo. While still burning, the fleet
   budget view must show the edge's burn from the OWNER's
   /debug/cluster (the SLO blob riding PeersV1.DebugInfo);
3. heal — clear the fault. The stranded queue drains to the owner
   (DRAIN_OVER_LIMIT force-apply), debt falls to 0, the alert must
   clear back to "ok" and the error budget must stop burning
   (remaining stabilizes above zero — the shrunk windows are sized so
   a bounded incident never exhausts the budget).

Acceptance evidence (ISSUE 17): `fired`, `fired_within_window`,
`fleet_budget_visible`, `cleared`, `budget_stopped_burning`. Prints one
`RESULT {json}` line.
"""
import os
import sys, json, time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def run() -> dict:
    import asyncio

    import aiohttp
    import jax

    from gubernator_tpu.api.types import Behavior, RateLimitReq
    from gubernator_tpu.client import GubernatorClient
    from gubernator_tpu.cluster import Cluster
    from gubernator_tpu.service.config import BehaviorConfig, DaemonConfig
    from gubernator_tpu.service.daemon import Daemon
    from gubernator_tpu.utils import faults

    N_KEYS = 32
    LIMIT = 200
    DURATION_MS = 60_000  # one window outlives the whole drill
    CHUNK = 50  # pump the full limit in 4 chunks per key
    SAMPLE_S = 0.25
    EVAL_WINDOW_S = 6.0  # the long fast window: the "one window" bound
    BAD_THRESHOLD = 0.1  # admission-accuracy spec default threshold
    STEADY_S = 45.0  # clean-sample runway before the fault
    # Merge-override (service/slo.py parse_slo_specs): keep the SLI and
    # threshold, shrink the windows to soak scale. Burn fractions
    # divide by the samples PRESENT in a window, and a fresh daemon
    # only has the samples it has lived — so the steady phase banks
    # STEADY_S of clean runway and the objective is loosened to 0.8 so
    # a seconds-long incident burns hard without exhausting the budget
    # — the point is to watch fast_burn fire AND clear, not to pin the
    # state at "exhausted".
    SLO_SPECS = json.dumps([
        {
            "id": "admission-accuracy",
            "objective": 0.8,
            "fast_windows": [3.0, EVAL_WINDOW_S],
            "slow_windows": [EVAL_WINDOW_S, 18.0],
            "fast_factor": 2.0,
            "slow_factor": 2.0,
            "budget_window_s": 900.0,
        }
    ])

    def req(i: int, hits: int) -> RateLimitReq:
        return RateLimitReq(
            name="slo_soak", unique_key=f"acct:{i}",
            duration=DURATION_MS, limit=LIMIT, hits=hits,
            behavior=int(Behavior.GLOBAL),
        )

    async def main():
        behaviors = BehaviorConfig(
            circuit_failure_threshold=3,
            circuit_open_base_s=0.2, circuit_open_max_s=2.0,
            global_sync_wait_s=0.1,
        )
        c = Cluster()
        for _ in range(3):
            c.daemons.append(
                await Daemon.spawn(
                    DaemonConfig(
                        cache_size=8192,
                        behaviors=behaviors,
                        admission_ttl_s=0.5,
                        slo_sample_interval_s=SAMPLE_S,
                        slo_specs=SLO_SPECS,
                    )
                )
            )
        c.rewire()
        session = aiohttp.ClientSession()
        try:
            owner = c.find_owning_daemon("slo_soak", "acct:0")
            edge = next(d for d in c.daemons if d is not owner)
            keys = [
                i for i in range(4000)
                if c.find_owning_daemon("slo_soak", f"acct:{i}") is owner
            ][:N_KEYS]
            assert len(keys) == N_KEYS
            loop = asyncio.get_running_loop()

            async def slo_poll() -> tuple:
                # The sampler's debt-ratio denominator is the
                # TTL-cached admission scan (cached_admission never
                # scans — GL009). Production keeps that cache warm via
                # the auditor / scrape cadence; this job plays that
                # role at the same rhythm.
                await loop.run_in_executor(
                    None,
                    lambda: edge.svc.engine.admission_snapshot(
                        max_age_s=0.2
                    ),
                )
                async with session.get(
                    f"http://{edge.http_address}/debug/slo"
                ) as r:
                    blob = await r.json()
                adm = {e["id"]: e for e in blob["slos"]}[
                    "admission-accuracy"
                ]
                debt = (
                    blob["slis"]
                    .get("admission_debt_ratio", {})
                    .get("last")
                )
                return blob, adm, debt

            # -- 1. steady: warm traffic, clean flush, SLO ok ----------
            plain = GubernatorClient(edge.grpc_address)
            for i in keys:
                (resp,) = await plain.get_rate_limits(
                    [req(i, 1)], timeout=10
                )
                assert resp.error == "", resp.error
            # let the queued warm hits flush to the owner, then bank
            # STEADY_S of clean (debt 0) samples — the budget window's
            # denominator only holds the samples the daemon has lived
            await asyncio.sleep(1.0)
            _, adm, debt = await slo_poll()
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < STEADY_S:
                await asyncio.sleep(1.0)
            _, adm, debt = await slo_poll()
            steady = {
                "state": adm["state"],
                "error_budget_remaining": adm["error_budget_remaining"],
                "debt_ratio": debt,
            }

            # -- 2. partition the owner; pump; debt pins near 1 --------
            faults.INJECTOR.partition(owner.grpc_address)
            t_partition = time.perf_counter()
            served = 0
            for _ in range(LIMIT // CHUNK):
                for i in keys:
                    (resp,) = await plain.get_rate_limits(
                        [req(i, CHUNK)], timeout=10
                    )
                    assert resp.error == "", resp.error
                    served += 1
            pump_dt = time.perf_counter() - t_partition
            partition = {
                "served": served,
                "pump_checks_per_s": round(served / pump_dt, 1),
            }

            first_bad_at = fired_at = None
            fired = None
            states_seen = set()
            while time.perf_counter() - t_partition < 30.0:
                blob, adm, debt = await slo_poll()
                states_seen.add(adm["state"])
                if (
                    first_bad_at is None
                    and debt is not None
                    and debt > BAD_THRESHOLD
                ):
                    first_bad_at = time.perf_counter()
                if adm["state"] == "fast_burn":
                    fired_at = time.perf_counter()
                    fired = {
                        "state": adm["state"],
                        "burn_rates": adm["burn_rates"],
                        "error_budget_remaining": adm[
                            "error_budget_remaining"
                        ],
                        "debt_ratio": debt,
                        "s_from_partition": round(
                            fired_at - t_partition, 2
                        ),
                        "s_from_first_bad": round(
                            fired_at - (first_bad_at or t_partition), 2
                        ),
                    }
                    break
                await asyncio.sleep(SAMPLE_S)
            fired_within = bool(
                fired is not None
                and fired["s_from_first_bad"] <= EVAL_WINDOW_S + 1.0
            )

            # fleet budget view DURING the incident: the OWNER's
            # /debug/cluster must show the edge's burn through the
            # DebugInfo SLO rider (owner->edge DebugInfo is not
            # faulted — only calls TO the owner are partitioned)
            async with session.get(
                f"http://{owner.http_address}/debug/cluster"
            ) as r:
                cluster = await r.json()
            peer_blob = cluster["peers"].get(edge.grpc_address) or {}
            fleet_row = (peer_blob.get("slo") or {}).get("slos", {}).get(
                "admission-accuracy"
            )
            fleet_visible = bool(
                fleet_row is not None
                and fleet_row["state"] in ("fast_burn", "slow_burn")
                and fleet_row["error_budget_remaining"] is not None
                and fleet_row["error_budget_remaining"] < 1.0
            )

            # -- 3. heal: the debt drains, alert clears ----------------
            faults.INJECTOR.clear()
            t_heal = time.perf_counter()
            cleared = None
            while time.perf_counter() - t_heal < 45.0:
                blob, adm, debt = await slo_poll()
                states_seen.add(adm["state"])
                if adm["state"] == "ok":
                    cleared = {
                        "state": adm["state"],
                        "error_budget_remaining": adm[
                            "error_budget_remaining"
                        ],
                        "debt_ratio": debt,
                        "cleared_s": round(
                            time.perf_counter() - t_heal, 2
                        ),
                    }
                    break
                await asyncio.sleep(SAMPLE_S)
            budget_stopped = False
            if cleared is not None:
                _, adm, _ = await slo_poll()
                r1 = adm["error_budget_remaining"]
                await asyncio.sleep(3.0)
                _, adm, _ = await slo_poll()
                r2 = adm["error_budget_remaining"]
                cleared["budget_then"] = r1
                cleared["budget_after"] = r2
                # with no new bad samples the bad count is frozen, so
                # remaining can only recover (rise) — never burn down
                budget_stopped = bool(
                    r1 is not None
                    and r2 is not None
                    and r1 > 0.0
                    and r2 >= r1 - 1e-9
                )
            await plain.close()

            return {
                "bench": "slo_soak",
                "metric": (
                    "admission-SLO burn-rate alert cycle under owner "
                    f"partition ({jax.default_backend()}, 3-daemon "
                    f"mesh, {N_KEYS} GLOBAL keys) pump checks/s"
                ),
                "value": partition["pump_checks_per_s"],
                "unit": "checks/s",
                "daemons": 3,
                "keys": N_KEYS,
                "limit": LIMIT,
                "duration_ms": DURATION_MS,
                "sample_interval_s": SAMPLE_S,
                "eval_window_s": EVAL_WINDOW_S,
                "steady": steady,
                "partition": partition,
                "fired_detail": fired,
                "fleet_row": fleet_row,
                "cleared_detail": cleared,
                "states_seen": sorted(states_seen),
                "fired": fired is not None,
                "fired_within_window": fired_within,
                "fleet_budget_visible": fleet_visible,
                "cleared": cleared is not None,
                "budget_stopped_burning": budget_stopped,
                "never_exhausted": "exhausted" not in states_seen,
            }
        finally:
            faults.INJECTOR.clear()
            await session.close()
            await c.stop()

    return asyncio.run(main())


r = run()
print("RESULT " + json.dumps(r))
