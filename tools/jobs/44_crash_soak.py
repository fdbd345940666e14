"""Crash soak: the standby-replication acceptance drill
(docs/robustness.md "Standby replication & crash recovery").

A 3-daemon mesh runs continuous Zipf-distributed load against keys
owned by one daemon (the victim). Mid-flight the victim is hard-killed
— its replication loops are frozen and it is partitioned, the
in-process stand-in for SIGKILL: no drain, no handover, no retire —
and the membership change promotes its standbys. The measured counter
loss across every driven key must be <= the loss bound the victim
PUBLISHED (gubernator_standby_loss_bound_hits) at the kill instant.
Afterwards the surviving pair keeps replicating: a fault-injected
standby drop (faults.OP_PEER_STANDBY) plus a deliberately corrupted
shadow must be found and repaired by anti-entropy, with a follow-up
digest exchange reporting zero mismatched regions (convergence).

Prints one `RESULT {json}` line and appends it to the benchmark ledger
(mode=crash_soak) with the auto-gate verdict as a `GATE {json}` line.
"""
import os
import sys, json, time, random

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def run() -> dict:
    import asyncio

    from gubernator_tpu.cluster import Cluster
    from gubernator_tpu.service import pb
    from gubernator_tpu.service.config import BehaviorConfig
    from gubernator_tpu.utils import faults

    NAME = "crash_soak"
    LIMIT = 10_000_000
    DURATION_MS = 600_000
    N_KEYS = 150
    LOAD_S = 4.0
    SHIP_S = 0.25

    async def main():
        c = await Cluster.start(
            3,
            behaviors=BehaviorConfig(
                standby_interval_s=SHIP_S,
                standby_promote_after_s=1.0,
                # AE runs on demand below (deterministic pass counting).
                standby_anti_entropy_interval_s=0.0,
                circuit_failure_threshold=3,
                circuit_open_base_s=0.2,
                circuit_open_max_s=1.0,
            ),
            cache_size=65536,
        )
        try:
            victim = c.find_owning_daemon(NAME, "victimkey")
            survivors = [d for d in c.daemons if d is not victim]
            driver = survivors[0]
            stub = driver.client()

            # Zipf-weighted victim-owned key set.
            keys = []
            for i in range(100_000):
                k = f"ck{i}"
                if c.find_owning_daemon(NAME, k) is victim:
                    keys.append(k)
                    if len(keys) >= N_KEYS:
                        break
            weights = [1.0 / (i + 1) ** 1.1 for i in range(len(keys))]
            rng = random.Random(42)

            async def hit(key, hits):
                msg = pb.pb.GetRateLimitsReq()
                msg.requests.append(
                    pb.pb.RateLimitReq(
                        name=NAME, unique_key=key, duration=DURATION_MS,
                        limit=LIMIT, hits=hits,
                    )
                )
                return (await stub.get_rate_limits(msg, timeout=10)).responses[0]

            # Continuous Zipf load: count a hit only when the victim
            # ACKED it (an error response consumed nothing).
            sent = dict.fromkeys(keys, 0)
            acked = 0
            t0 = time.perf_counter()
            t_end = t0 + LOAD_S
            while time.perf_counter() < t_end:
                for k in rng.choices(keys, weights=weights, k=64):
                    resp = await hit(k, 1)
                    if not resp.error:
                        sent[k] += 1
                        acked += 1
            load_rate = acked / (time.perf_counter() - t0)

            # Replication must actually be flowing before the kill.
            await asyncio.sleep(2 * SHIP_S)
            shadow_rows = sum(
                e["keys"]
                for d in survivors
                for e in d.svc.standby.summary()["shadows"].values()
            )

            # A final burst the ship loop gets no chance to ack: these
            # hits are the dirt the kill actually loses, so the bound
            # (and usually the measured loss) is nonzero — the check
            # must not pass vacuously on a quiesced owner.
            for k in rng.choices(keys, weights=weights, k=128):
                resp = await hit(k, 1)
                if not resp.error:
                    sent[k] += 1
                    acked += 1

            # --- hard kill. Freeze the victim's replication FIRST (the
            # bound stops moving), read the published bound, then cut it
            # off. No close(), no drain, no retire — the SIGKILL shape.
            sb = victim._standby
            for t in (sb._ship_task, sb._ae_task):
                if t is not None:
                    t.cancel()
            bound_at_kill = sb.loss_bound_hits()
            faults.INJECTOR.partition(victim.grpc_address)
            victim_addr = victim.grpc_address

            # Membership change (discovery notices the death): survivors
            # see the victim leave the ring unretired -> promotion.
            c.daemons.remove(victim)
            c.rewire()
            deadline = time.monotonic() + 10
            promoted = False
            while time.monotonic() < deadline:
                if all(
                    victim_addr not in d.svc.standby.summary()["shadows"]
                    for d in survivors
                ) and any(
                    d.svc.standby.summary()["promotions"] > 0
                    for d in survivors
                ):
                    promoted = True
                    break
                await asyncio.sleep(0.1)

            # --- measured loss vs the published bound. hits=0 probes
            # read each key's counter at its post-death owner.
            consumed = 0
            for k in keys:
                resp = await hit(k, 0)
                if not resp.error:
                    consumed += LIMIT - resp.remaining
            loss = acked - consumed
            loss_ok = loss <= bound_at_kill

            # --- anti-entropy: fault-injected standby drops plus a
            # corrupted shadow must be found and repaired.
            a, b = survivors
            faults.INJECTOR.add_rule(
                faults.FaultRule(
                    target=b.grpc_address, op=faults.OP_PEER_STANDBY,
                    error_rate=1.0, max_injections=4,
                )
            )
            for k in keys[:40]:
                await hit(k, 1)
            await asyncio.sleep(4 * SHIP_S)  # ships flow; 4 legs dropped
            faults.INJECTOR.clear()
            dropped_legs = int(
                sum(
                    a.svc.metrics.standby_ship_errors.labels(r).get()
                    for r in ("circuit_open", "deadline", "send_error")
                )
            )
            # Corrupt b's shadow of a (simulated restart / bit rot).
            shadow = b.svc.standby._shadow.get(a.grpc_address)
            corrupted = 0
            if shadow is not None:
                for k in list(shadow.rows)[:5]:
                    del shadow.rows[k]
                    corrupted += 1
            # Quiesce pending deltas, then: pass 1 repairs, pass 2 clean.
            await asyncio.sleep(4 * SHIP_S)
            r1 = await a.svc.standby.anti_entropy_once()
            r2 = await a.svc.standby.anti_entropy_once()
            repaired = r1["mismatched_regions"]
            converged = r2["mismatched_regions"] == 0

            ok = bool(
                promoted and loss_ok and shadow_rows > 0
                and (corrupted == 0 or repaired > 0) and converged
            )
            return {
                "bench": "crash_soak",
                "metric": f"crash soak load (cpu, {N_KEYS} zipf keys)",
                "value": round(load_rate, 1),
                "unit": "checks/s",
                "daemons": 3,
                "keys": len(keys),
                "acked_hits": acked,
                "shadow_rows_before_kill": shadow_rows,
                "bound_at_kill": bound_at_kill,
                "measured_loss": loss,
                "loss_within_bound": loss_ok,
                "promoted": promoted,
                "standby_legs_failed": dropped_legs,
                "shadow_rows_corrupted": corrupted,
                "ae_regions_repaired": repaired,
                "ae_converged": converged,
                "crash_soak_ok": ok,
            }
        finally:
            faults.INJECTOR.clear()
            await c.stop()
            if victim not in c.daemons:
                await victim.close()

    return asyncio.run(main())


r = run()
print("RESULT " + json.dumps(r))

from gubernator_tpu.utils import ledger

ledger.append(r, job="44_crash_soak", mode="crash_soak", platform="cpu")
print("GATE " + json.dumps(ledger.gate(job="44_crash_soak", mode="crash_soak")))
sys.exit(0 if r.get("crash_soak_ok") else 1)
