"""Table-census capacity planner (docs/monitoring.md "Table census"):
soak a DeviceEngine with a skewed keyspace — a small always-hot set, a
warm working set, and a stream of one-shot short-window tail keys —
under a controlled clock, sampling the census each simulated minute.
The report is the evidence set the paged-table design (ROADMAP item 1)
needs: how the cold set grows at each idleness multiplier, how much
HBM expired residents waste, how fast slots churn (insert / evict /
recycle rates from the ledger), and how skew concentrates occupancy
across heatmap regions.

Prints one `RESULT {json}` line like the other jobs.
"""
import os
import sys, json

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def run() -> dict:
    import random

    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig

    T0 = 1_753_700_000_000
    clock = {"now": T0}
    eng = DeviceEngine(
        EngineConfig(num_groups=1 << 12, ways=8, batch_size=256,
                     batch_wait_s=0.002),
        now_fn=lambda: clock["now"],
    )
    rnd = random.Random(34)

    def reqs(keys, duration, limit=1_000_000):
        return [
            RateLimitReq(name="census_soak", unique_key=k,
                         duration=duration, limit=limit, hits=1)
            for k in keys
        ]

    hot = [f"hot{i}" for i in range(256)]  # hit every minute
    warm = [f"warm{i}" for i in range(4096)]  # hit every 4th minute
    tail_seq = 0

    minutes = 20
    samples = []
    try:
        for minute in range(minutes):
            clock["now"] = T0 + minute * 60_000
            eng.check_batch(reqs(hot, duration=3_600_000))
            if minute % 4 == 0:
                eng.check_batch(reqs(warm, duration=3_600_000))
            # tail: fresh one-shot keys with 30s windows — they expire
            # before the next sample and become waste, then recycles
            tail = [f"tail{tail_seq + i}" for i in range(512)]
            tail_seq += len(tail)
            rnd.shuffle(tail)
            eng.check_batch(reqs(tail, duration=30_000))

            c = eng.table_census(max_age_s=0)
            churn = c["churn"]
            samples.append(
                {
                    "minute": minute,
                    "live": c["live"],
                    "occupancy": round(c["occupancy"], 4),
                    "waste_frac": round(c["waste_frac"], 4),
                    "cold_frac": {
                        str(e["multiplier"]): round(e["frac"], 4)
                        for e in c["cold"]
                    },
                    "heatmap_min": min(c["heatmap"]),
                    "heatmap_max": max(c["heatmap"]),
                    "insert_per_s": churn["insert_per_s"],
                    "evict_per_s": churn["evict_per_s"],
                    "recycle_per_s": churn["recycle_per_s"],
                }
            )

        final = eng.table_census(max_age_s=0)
        total_inserts = sum(s["insert_per_s"] for s in samples)
        return {
            "bench": "table_census",
            "layout": final["layout"],
            "slots": final["slots"],
            "bytes_per_slot": final["bytes_per_slot"],
            "minutes": minutes,
            "keys": {"hot": len(hot), "warm": len(warm), "tail": tail_seq},
            "samples": samples,
            "final": {
                "live": final["live"],
                "occupancy": round(final["occupancy"], 4),
                "waste": final["waste"],
                "waste_frac": round(final["waste_frac"], 4),
                "max_full_run": final["max_full_run"],
                "full_group_ratio": round(final["full_group_ratio"], 4),
                # the capacity-planning punchline: HBM a cold tier
                # would free at each demotion aggressiveness
                "reclaimable_bytes": {
                    str(e["multiplier"]): e["reclaimable_bytes"]
                    for e in final["cold"]
                },
                "age_ms_hist": final["age_ms_hist"],
                "idle_ms_hist": final["idle_ms_hist"],
            },
            "cold_compiles": eng.metrics.cold_compiles,
            "churn_observed": total_inserts > 0,
        }
    finally:
        eng.close()


r = run()
print("RESULT " + json.dumps(r))
