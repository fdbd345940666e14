"""Device-resource observatory soak (docs/monitoring.md "Device
resources"): drive a DeviceEngine through the serving, snapshot/restore
and readthrough-inject paths, then report what the run actually cost in
device resources — per-subsystem HBM attribution + headroom from
utils/devicemem, the host<->device transfer ledger (bytes, latency and
sustained bandwidth per direction/purpose), and compile telemetry with
retrace attribution. The punchline numbers: HBM headroom after a full
warm-up, and sustainable d2h serve bandwidth (the demux readback is the
serving path's host<->device bottleneck).

Prints one `RESULT {json}` line like the other jobs.
"""
import os
import sys, json

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def run() -> dict:
    import time

    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig

    eng = DeviceEngine(
        EngineConfig(num_groups=1 << 12, ways=8, batch_size=256,
                     batch_wait_s=0.002)
    )

    def reqs(keys, limit=1_000_000):
        return [
            RateLimitReq(name="device_soak", unique_key=k,
                         duration=3_600_000, limit=limit, hits=1)
            for k in keys
        ]

    rounds = 40
    keys_per_round = 512
    t0 = time.monotonic()
    try:
        decided = 0
        for r in range(rounds):
            batch = reqs([f"soak{r % 8}_{i}" for i in range(keys_per_round)])
            decided += len(eng.check_batch(batch))
        # Exercise the snapshot + inject purposes so the ledger has all
        # five rows, not just serve/warmup/census.
        from gubernator_tpu.store.store import ItemSnapshot

        snap = eng.snapshot()
        now_ms = int(time.time() * 1000)
        eng.inject_snapshots([
            ItemSnapshot(key=f"inject{i}", algorithm=0, limit=1_000_000,
                         duration=3_600_000, remaining=5, stamp=now_ms,
                         expire_at=now_ms + 3_600_000)
            for i in range(64)
        ])
        eng.restore(snap)
        wall_s = time.monotonic() - t0

        mem = eng.device_memory()
        transfers = eng.metrics.transfer_snapshot()
        serve = transfers.get("d2h/serve", {})

        from gubernator_tpu.utils import compilecache

        return {
            "bench": "device_observatory",
            "decisions": decided,
            "wall_s": round(wall_s, 3),
            "memory": {
                "source": mem["source"],
                "bytes_in_use": mem["bytes_in_use"],
                "bytes_limit": mem["bytes_limit"],
                "headroom_bytes": mem["headroom_bytes"],
                "headroom_frac": round(mem["headroom_frac"], 4),
                "subsystems": mem["subsystems"],
                "unattributed_bytes": mem["unattributed_bytes"],
            },
            "transfers": transfers,
            # sustainable serve readback bandwidth over the whole soak
            "serve_d2h_bytes_per_s": round(
                serve.get("bytes", 0) / max(wall_s, 1e-9), 1
            ),
            "compile": compilecache.cache_stats(),
            "cold_compiles": eng.metrics.cold_compiles,
        }
    finally:
        eng.close()


r = run()
print("RESULT " + json.dumps(r))
