"""Paged-table capacity wall study (docs/architecture.md "Paged table"):
the same Zipf-skewed trace through (a) a flat all-resident engine — the
oracle and latency baseline — and (b) a paged engine whose logical
table is >10x its HBM-resident page budget, so the cold majority of the
keyspace lives in the host-DRAM tier and hot pages cycle through the
resident frames on demand.

Acceptance evidence (ISSUE 12): `keyspace_ratio` >= 10, `p99_ratio`
(paged p99 / all-resident p99 on the skewed serving phase) <= 2, and
`zero_loss` — after the measured phase every key's counter in the paged
engine equals the flat engine's, demote/promote churn included.

Geometry note: a single wave's distinct-page working set must fit the
page budget (PageBudgetError otherwise), so the trace is served in
8-request calls against a 12-frame budget — worst case 8 distinct
pages per wave, with 4 frames of slack for the demoter.

Prints one `RESULT {json}` line.
"""
import os
import sys, json, time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def run() -> dict:
    import numpy as np

    import jax

    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig

    platform = jax.devices()[0].platform
    NUM_GROUPS, WAYS = 1 << 12, 8
    PAGE_GROUPS, BUDGET = 32, 12  # 128 logical pages, 12 resident frames
    CALL = 8  # requests per check_batch call (page working set bound)
    N_KEYS = 6_000  # spans every logical page; ~2x the resident slots
    MEASURED_CALLS = 400

    keyspace_ratio = NUM_GROUPS / float(BUDGET * PAGE_GROUPS)

    def mk_engine(paged: bool) -> DeviceEngine:
        kw = dict(
            num_groups=NUM_GROUPS, ways=WAYS, batch_size=64,
            batch_wait_s=0.001,
        )
        if paged:
            kw.update(
                page_groups=PAGE_GROUPS, page_budget=BUDGET,
                page_demote_interval_s=0.5, page_free_target=2,
            )
        return DeviceEngine(EngineConfig(**kw))

    def req(i: int, hits: int = 1) -> RateLimitReq:
        return RateLimitReq(
            name="paged_soak", unique_key=f"acct:{i}",
            duration=3_600_000, limit=1_000_000, hits=hits,
        )

    # Zipf-weighted key ranks: the hot head concentrates on few pages
    # (they stay resident), the cold tail sweeps the whole keyspace.
    rng = np.random.default_rng(36)
    w = 1.0 / np.arange(1, N_KEYS + 1, dtype=np.float64) ** 1.1
    w /= w.sum()
    trace = rng.choice(N_KEYS, size=MEASURED_CALLS * CALL, p=w)

    def drive(eng: DeviceEngine) -> dict:
        # populate: every key once -> all 128 pages hold live rows
        for i in range(0, N_KEYS, CALL):
            eng.check_batch([req(k) for k in range(i, min(i + CALL, N_KEYS))])
        # measured skewed serving
        lat = []
        t0 = time.perf_counter()
        for c in range(MEASURED_CALLS):
            chunk = trace[c * CALL:(c + 1) * CALL]
            s = time.perf_counter()
            for rl in eng.check_batch([req(int(k)) for k in chunk]):
                assert rl.error == "", rl.error
            lat.append(time.perf_counter() - s)
        dt = time.perf_counter() - t0
        # zero-loss probe: every key's exact remaining
        remaining = []
        for i in range(0, N_KEYS, CALL):
            remaining.extend(
                rl.remaining
                for rl in eng.check_batch(
                    [req(k, hits=0) for k in range(i, min(i + CALL, N_KEYS))]
                )
            )
        return {
            "throughput": (MEASURED_CALLS * CALL) / dt,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "remaining": remaining,
        }

    flat_eng = mk_engine(paged=False)
    try:
        flat = drive(flat_eng)
    finally:
        flat_eng.close()

    paged_eng = mk_engine(paged=True)
    try:
        paged = drive(paged_eng)
        pager = paged_eng._pager
        census = paged_eng.table_census(max_age_s=0)
        pages = dict(census["pages"])
        pages.pop("page_map", None)
        tier_live = {t: c["live"] for t, c in census["tiers"].items()}
    finally:
        paged_eng.close()

    zero_loss = paged["remaining"] == flat["remaining"]
    p99_ratio = paged["p99_ms"] / flat["p99_ms"] if flat["p99_ms"] else None
    return {
        "bench": "paged_table",
        "metric": (
            f"paged-table skewed serving ({platform}, "
            f"{keyspace_ratio:.1f}x keyspace vs HBM page budget) decisions/s"
        ),
        "value": round(paged["throughput"], 1),
        "unit": "decisions/s",
        "platform": platform,
        "geometry": {
            "num_groups": NUM_GROUPS, "ways": WAYS,
            "page_groups": PAGE_GROUPS, "page_budget": BUDGET,
            "logical_pages": NUM_GROUPS // PAGE_GROUPS,
            "keys": N_KEYS,
        },
        "keyspace_ratio": round(keyspace_ratio, 2),
        "flat": {k: round(v, 3) if isinstance(v, float) else None
                 for k, v in flat.items() if k != "remaining"},
        "paged": {k: round(v, 3) if isinstance(v, float) else None
                  for k, v in paged.items() if k != "remaining"},
        "p99_ratio": round(p99_ratio, 3) if p99_ratio else None,
        "p99_within_2x": bool(p99_ratio is not None and p99_ratio <= 2.0),
        "zero_loss": bool(zero_loss),
        "pager": {
            "demotes": pager.demotes, "promotes": pager.promotes,
            "binds": pager.binds,
        },
        "tier_live": tier_live,
        "pages": pages,
    }


r = run()
print("RESULT " + json.dumps(r))
