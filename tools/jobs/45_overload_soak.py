"""Overload soak: the DoS-flood + retry-storm acceptance drill
(docs/robustness.md "Overload control & brownout").

A 3-daemon mesh runs with the overload control plane armed
(GUBER_OVERLOAD semantics: bounded deadline-aware intake, CoDel
tenant-fair shedding, retry budgets, brownout ladder) and the SLO
observatory off, so the ladder is driven purely by the intake
controller's sustained-standing-queue signal — deterministic on CPU.

Well-behaved tenants drive closed-loop, deadline-carrying load over
real gRPC through the budgeted-retry client to establish a goodput +
latency baseline. Then a single flood tenant opens up at 10x the
baseline offered rate, open-loop, injected straight into the owner's
engine intake (per-item check_async — on CPU the gRPC stack saturates
long before the engine does, so an in-process flood is the only way a
Python driver can actually stand a queue); a reaper re-dispatches the
flood's typed sheds through a service/overload.RetryBudget, the same
retry-amplification shape a misbehaving retrying client produces.

The GATE asserts the paper-grade overload contract:
  - well-behaved-tenant goodput under flood >= 70% of baseline,
  - admitted-work p99 under flood <= 2x baseline,
  - intake queue depth bounded by the configured limit throughout,
  - the brownout ladder escalates during the flood and recovers to
    level 0 after it stops.

Prints one `RESULT {json}` line and appends it to the benchmark ledger
(mode=overload_soak) with the auto-gate verdict as a `GATE {json}` line.
"""
import os
import sys, json, time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def run() -> dict:
    import asyncio

    from gubernator_tpu.api.types import RateLimitReq, is_retryable_error
    from gubernator_tpu.client import GubernatorClient
    from gubernator_tpu.cluster import Cluster
    from gubernator_tpu.service.config import BehaviorConfig
    from gubernator_tpu.service.overload import RetryBudget

    LIMIT = 1_000_000_000
    DURATION_MS = 600_000
    INTAKE_LIMIT = 256      # queue entries; the depth bound the GATE holds
    TARGET_MS = 15.0        # CoDel standing-queue target
    GOOD_WORKERS = 4        # closed-loop well-behaved tenant drivers
    GOOD_BATCH = 2          # items per well-behaved call
    FLOOD_X = 10.0          # flood rate vs measured baseline offered
    FLOOD_OUTSTANDING = 4000  # open-loop cap; past it the sender drops
    WARM_S = 4.0
    BASE_S = 4.0
    FLOOD_S = 20.0
    RECOVER_S = 75.0
    N_KEYS = 24             # per tenant, all owned by the same daemon

    async def main():
        c = await Cluster.start(
            3,
            behaviors=BehaviorConfig(
                # Throttle the engine's per-cycle appetite so the flood
                # can out-run the pump on CPU (~4 items per cycle).
                batch_wait_s=0.004,
                batch_limit=4,
            ),
            cache_size=8192,
            overload=True,
            intake_limit=INTAKE_LIMIT,
            intake_target_ms=TARGET_MS,
            # Ladder driven by the intake signal alone: no SLO burn /
            # watchdog coupling, so recovery is decided by the queue.
            slo_sample_interval_s=0.0,
        )
        good = None
        try:
            owner = c.daemons[0]
            for d in c.daemons:
                # Evaluate fast (short chaos window while the ladder
                # climbs) and hold a reached level through the flood
                # instead of probing back down mid-storm (the default
                # 2s hysteresis would flap L3<->L2 against a 20s flood;
                # the drill wants one clean escalate/recover cycle).
                d._overload.interval_s = 0.1
                d._overload.hysteresis = 150

            def owned_keys(prefix: str) -> list:
                ks = []
                for i in range(100_000):
                    k = f"{prefix}{i}"
                    if c.find_owning_daemon(prefix, k) is owner:
                        ks.append(k)
                        if len(ks) >= N_KEYS:
                            break
                return ks

            good_keys = owned_keys("good")
            flood_keys = owned_keys("flood")

            # Well-behaved tenant: the real budgeted-retry client over
            # gRPC (typed-shed re-dispatch honoring retry_after_ms).
            good = GubernatorClient(
                owner.grpc_address, retries=3, retry_budget=0.1
            )

            deadline_ms = {"v": 0}  # good-tenant per-call deadline; 0=off

            def reqs(name, keys, j, n):
                md = {}
                if name == "good" and deadline_ms["v"]:
                    md = {
                        "deadline_ms": str(
                            int(time.time() * 1000) + deadline_ms["v"]
                        )
                    }
                return [
                    RateLimitReq(
                        name=name, unique_key=keys[(j + i) % len(keys)],
                        hits=1, limit=LIMIT, duration=DURATION_MS,
                        metadata=dict(md),
                    )
                    for i in range(n)
                ]

            # -- well-behaved tenant drivers (closed loop) ------------
            stats = {"acked": 0, "offered": 0, "lat": []}
            stop_good = asyncio.Event()

            async def good_worker(w: int):
                j = w * 7
                while not stop_good.is_set():
                    j += GOOD_BATCH
                    stats["offered"] += GOOD_BATCH
                    t0 = time.perf_counter()
                    try:
                        out = await good.get_rate_limits(
                            reqs("good", good_keys, j, GOOD_BATCH),
                            timeout=10,
                        )
                    except Exception:
                        continue
                    dt = time.perf_counter() - t0
                    n_ok = sum(1 for r in out if not r.error)
                    if n_ok:
                        stats["acked"] += n_ok
                        stats["lat"].append(dt)

            def window_reset():
                snap = dict(stats, lat=list(stats["lat"]))
                stats["acked"] = 0
                stats["offered"] = 0
                stats["lat"] = []
                return snap

            def p99(lat):
                if not lat:
                    return float("inf")
                s = sorted(lat)
                return s[min(len(s) - 1, int(0.99 * (len(s) - 1)) + 1)]

            workers = [
                asyncio.ensure_future(good_worker(w))
                for w in range(GOOD_WORKERS)
            ]

            # -- phase A: baseline ------------------------------------
            await asyncio.sleep(WARM_S)  # compile caches / bucket warmup
            window_reset()
            t0 = time.perf_counter()
            await asyncio.sleep(BASE_S)
            base = window_reset()
            base_dt = time.perf_counter() - t0
            goodput_base = base["acked"] / base_dt
            offered_base = base["offered"] / base_dt
            p99_base = p99(base["lat"])

            # From here the good tenant carries an SLO-shaped caller
            # deadline: work the queue cannot serve in time is refused
            # (admit) or dropped at pickup instead of being served
            # uselessly late. Sized from the measured baseline.
            deadline_ms["v"] = int(
                min(1000, max(80, 1.5 * p99_base * 1000))
            )

            # -- phase B: 10x single-tenant flood, open loop ----------
            ladder = {"max_level": 0, "max_depth": 0, "http_level": None}
            stop_sample = asyncio.Event()

            async def sampler():
                while not stop_sample.is_set():
                    ladder["max_depth"] = max(
                        ladder["max_depth"], owner.engine.queue_depth()
                    )
                    lv = owner.svc.overload.debug_info()["level"]
                    ladder["max_level"] = max(ladder["max_level"], lv)
                    await asyncio.sleep(0.05)

            sample_task = asyncio.ensure_future(sampler())

            flood_rate = FLOOD_X * offered_base  # items/s, open loop
            flood_budget = RetryBudget(ratio=0.1)
            outstanding: list = []  # in-flight flood futures
            flood_sent = 0
            flood_retries = 0
            flood_client_dropped = 0

            def flood_one(j):
                nonlocal flood_sent
                flood_budget.record(1.0)
                flood_sent += 1
                return owner.engine.check_async(
                    reqs("flood", flood_keys, j, 1)[0]
                )

            def reap():
                """Harvest finished flood futures; re-dispatch typed
                sheds through the retry budget — the amplification a
                retry-storming client would apply."""
                nonlocal flood_retries
                live = []
                for f, retried in outstanding:
                    if not f.done():
                        live.append((f, retried))
                        continue
                    r = f.result()
                    if (
                        r.error and not retried
                        and is_retryable_error(r.error)
                        and flood_budget.try_spend()
                    ):
                        flood_retries += 1
                        nf = owner.engine.check_async(
                            reqs("flood", flood_keys, flood_sent, 1)[0]
                        )
                        live.append((nf, True))
                outstanding[:] = live

            t0 = time.perf_counter()
            t_end = t0 + FLOOD_S
            due = 0.0
            last = t0
            while True:
                now = time.perf_counter()
                if now >= t_end:
                    break
                due += flood_rate * (now - last)
                last = now
                n = int(due)
                due -= n
                for _ in range(n):
                    # Open loop: the sender never waits on responses;
                    # past the outstanding cap it drops on the floor
                    # (client-side overflow, counted, not paced).
                    if len(outstanding) >= FLOOD_OUTSTANDING:
                        flood_client_dropped += 1
                        continue
                    outstanding.append((flood_one(flood_sent), False))
                reap()
                await asyncio.sleep(0.02)
            flood_dt = time.perf_counter() - t0
            under = window_reset()
            goodput_flood = under["acked"] / flood_dt
            p99_flood = p99(under["lat"])

            # The debug endpoint is part of the contract: the ladder
            # level must be visible over HTTP while the flood is hot.
            import urllib.request

            def fetch_debug():
                with urllib.request.urlopen(
                    f"http://{owner.http_address}/debug/overload", timeout=5
                ) as r:
                    return json.loads(r.read())

            dbg = await asyncio.to_thread(fetch_debug)
            ladder["http_level"] = dbg.get("level")
            shed_counts = dict(dbg.get("intake", {}).get("shed", {}))

            # -- phase C: recovery ------------------------------------
            level_final = owner.svc.overload.debug_info()["level"]
            deadline = time.monotonic() + RECOVER_S
            while time.monotonic() < deadline:
                level_final = owner.svc.overload.debug_info()["level"]
                if level_final == 0:
                    break
                await asyncio.sleep(0.25)
            stop_sample.set()
            stop_good.set()
            await asyncio.gather(sample_task, *workers)

            goodput_ok = goodput_flood >= 0.70 * goodput_base
            p99_ok = p99_flood <= 2.0 * p99_base
            depth_ok = ladder["max_depth"] <= INTAKE_LIMIT
            escalated = ladder["max_level"] >= 1
            recovered = level_final == 0
            ok = bool(
                goodput_ok and p99_ok and depth_ok
                and escalated and recovered
            )
            return {
                "bench": "overload_soak",
                "metric": (
                    f"well-behaved goodput under 10x flood (cpu, "
                    f"{GOOD_WORKERS} workers)"
                ),
                "value": round(goodput_flood, 1),
                "unit": "checks/s",
                "daemons": 3,
                "intake_limit": INTAKE_LIMIT,
                "goodput_baseline": round(goodput_base, 1),
                "goodput_flood": round(goodput_flood, 1),
                "goodput_ratio": round(goodput_flood / goodput_base, 3),
                "p99_baseline_ms": round(p99_base * 1000, 1),
                "p99_flood_ms": round(p99_flood * 1000, 1),
                "good_deadline_ms": deadline_ms["v"],
                "flood_offered_rate": round(flood_sent / flood_dt, 1),
                "flood_retries": flood_retries,
                "flood_client_dropped": flood_client_dropped,
                "max_queue_depth": ladder["max_depth"],
                "max_ladder_level": ladder["max_level"],
                "http_ladder_level": ladder["http_level"],
                "final_ladder_level": level_final,
                "shed_counts": shed_counts,
                "goodput_ok": goodput_ok,
                "p99_ok": p99_ok,
                "depth_ok": depth_ok,
                "escalated": escalated,
                "recovered": recovered,
                "overload_soak_ok": ok,
            }
        finally:
            if good is not None:
                await good.close()
            await c.stop()

    return asyncio.run(main())


r = run()
print("RESULT " + json.dumps(r))

from gubernator_tpu.utils import ledger

ledger.append(r, job="45_overload_soak", mode="overload_soak", platform="cpu")
print("GATE " + json.dumps(ledger.gate(job="45_overload_soak", mode="overload_soak")))
sys.exit(0 if r.get("overload_soak_ok") else 1)
