"""Consistency soak (docs/monitoring.md "Consistency"): drive GLOBAL
traffic through a 3-daemon mesh from non-owner replicas, then measure
the eventual-consistency window the observatory instruments —
end-to-end propagation lag p50/p99 at each replica, per-leg counts,
and a full divergence-audit pass from every owner which must come back
clean (zero divergence, zero max staleness) once traffic quiesces.

Prints one `RESULT {json}` line like the other jobs.
"""
import os
import re, sys, json, time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def run() -> dict:
    import asyncio

    from gubernator_tpu.api.types import Behavior
    from gubernator_tpu.cluster import Cluster
    from gubernator_tpu.service import pb
    from gubernator_tpu.service.config import BehaviorConfig

    async def main():
        c = await Cluster.start(
            3,
            behaviors=BehaviorConfig(global_sync_wait_s=0.05),
            cache_size=65536,
        )
        try:
            name = "consistency_soak"
            keys = [f"soak{i}" for i in range(64)]

            async def hit(daemon, key, hits):
                msg = pb.pb.GetRateLimitsReq()
                msg.requests.append(
                    pb.pb.RateLimitReq(
                        name=name, unique_key=key, duration=600_000,
                        limit=10_000_000, hits=hits,
                        behavior=int(Behavior.GLOBAL),
                    )
                )
                await daemon.client().get_rate_limits(msg, timeout=10)

            # Soak: every key hit from a NON-owner (so each hit rides the
            # full queue -> owner apply -> broadcast -> inject pipeline).
            t_end = time.monotonic() + 15.0
            rounds = 0
            while time.monotonic() < t_end:
                for k in keys:
                    owner = c.find_owning_daemon(name, k)
                    hitter = next(d for d in c.daemons if d is not owner)
                    await hit(hitter, k, 1)
                rounds += 1

            # Let the last flush cycle land everywhere before measuring.
            await asyncio.sleep(1.0)

            per_daemon = []
            for d in c.daemons:
                m = d.svc.metrics
                lag = m.global_propagation_lag.summary(qs=(0.5, 0.99))
                text = m.render().decode()
                legs = {}
                for leg in (
                    "hit_queue_wait", "owner_apply",
                    "broadcast_fanout", "replica_inject",
                ):
                    mt = re.search(
                        r'gubernator_global_sync_leg_duration_count'
                        r'\{leg="%s"\} ([0-9.e+]+)' % leg,
                        text,
                    )
                    legs[leg] = int(float(mt.group(1))) if mt else 0
                per_daemon.append(
                    {
                        "address": d.grpc_address,
                        "propagation_count": int(lag["count"]),
                        "propagation_p50_ms": round(lag["p50"] * 1e3, 3),
                        "propagation_p99_ms": round(lag["p99"] * 1e3, 3),
                        "leg_counts": legs,
                    }
                )

            # Divergence audit from every owner: after quiesce the mesh
            # must be convergent — transport-level ledger vs arrival map.
            audits = []
            for d in c.daemons:
                auditor = getattr(d.svc, "auditor", None)
                if auditor is None:
                    continue
                s = await auditor.audit_once()
                audits.append(
                    {
                        "address": d.grpc_address,
                        "max_staleness_ms": s["max_staleness_ms"],
                        "divergence": s["divergence"],
                    }
                )
            converged = all(
                a["max_staleness_ms"] == 0
                and not any(a["divergence"].values())
                for a in audits
            )

            return {
                "bench": "consistency_soak",
                "daemons": 3,
                "keys": len(keys),
                "rounds": rounds,
                "hits": rounds * len(keys),
                "per_daemon": per_daemon,
                "audits": audits,
                "converged_after_quiesce": converged,
            }
        finally:
            await c.stop()

    return asyncio.run(main())


r = run()
print("RESULT " + json.dumps(r))
