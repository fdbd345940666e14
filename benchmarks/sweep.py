#!/usr/bin/env python3
"""The rate sweep that fixes an open-loop cell's rate: once, on the chip.

    python benchmarks/sweep.py --workload batching-10k.steady --seconds 10
    python benchmarks/sweep.py --workload batching-10k.steady --seconds 51 \
        --descend 90 80 70 60 --windows 6

One server, one process. The first form finds the knee roughly: rates
doubling from ``--start`` until one is not sustained, then bisected
``--bisect`` times. A rate is sustained when no call failed, the backlog
did not grow (the last quarter's median latency is under twice the first
quarter's, and everything in flight at the close drained within a second)
and the generator kept time (p99 lateness under a tenth of the median
latency).

The server also collapses now and then at rates it sustains for ten
seconds (PERF.md), so the second form decides: at the cell's own window,
from the highest rate down, ``--windows`` windows in a row at each rate;
the first rate at which none collapsed (a failed call, a p99 of a second or
more, a drain of a second or more, or a backlog that grew as above) is the
highest the server holds. Four fifths of it goes into the traffic file by
hand, and the table into PERF.md. The benchmark's own runs never search
for a rate.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks import manifest, run as harness, stats, traffic  # noqa: E402
from benchmarks.daemon import BenchFailure, Daemon, http_json, require  # noqa: E402


def one_rate(rate, seed, seconds, traf, ks, daemon, work) -> dict:
    traf = dict(traf, rate_calls_per_s=rate)
    plan = traffic.build_plan(traf, ks, seed, seconds)
    n_workers = int(traf.get("workers", 1))
    deadline_s = harness.call_deadline_s(traf)
    workers = harness.start_workers(plan, [daemon.grpc_addr], n_workers, work,
                                    deadline_s)
    try:
        harness.workers_ready(workers)
        harness.run_window(workers, seconds)
    finally:
        for w in workers:
            w.kill()
    res = harness.gather(n_workers, work, plan)
    lat = np.where(res["ok"], (res["done"] - res["due"]) * 1000.0,
                   deadline_s * 1000.0)
    late = (res["sent"] - res["due"]) * 1000.0
    order = np.argsort(res["due"])
    q = max(len(order) // 4, 1)
    first, last = lat[order[:q]], lat[order[-q:]]
    row = {
        "rate": rate, "calls": len(lat), "failed": int(np.sum(~res["ok"])),
        "p50_ms": stats.percentile(lat, 50), "p99_ms": stats.percentile(lat, 99),
        "first_q_p50_ms": stats.percentile(first, 50),
        "last_q_p50_ms": stats.percentile(last, 50),
        "drain_s": float(res["done"].max() - seconds),
        "late_p99_ms": stats.percentile(late, 99),
    }
    row["p95_ms"] = stats.percentile(lat, 95)
    row["mean_ms"] = float(lat.mean())
    row["max_ms"] = float(lat.max())
    row["sustained"] = bool(
        row["failed"] == 0
        and row["last_q_p50_ms"] < 2.0 * row["first_q_p50_ms"]
        and row["drain_s"] < 1.0
        and row["late_p99_ms"] < 0.1 * row["p50_ms"]
    )
    # a collapse is the server's; a late generator spoils a window, not the server
    row["collapsed"] = bool(
        row["failed"] > 0 or row["p99_ms"] >= 1000.0 or row["drain_s"] >= 1.0
        or row["last_q_p50_ms"] >= 2.0 * row["first_q_p50_ms"])
    worst = np.argsort(-late)[:4]
    row_worst = " ".join(
        f"(due={res['due'][i]:.3f}s late={late[i]:.2f}ms lat={lat[i]:.2f}ms)"
        for i in worst)
    print(f"sweep rate={rate} latest: {row_worst}", flush=True)
    slow = np.argsort(-lat)[:4]
    print(f"sweep rate={rate} slowest: " + " ".join(
        f"(due={res['due'][i]:.3f}s lat={lat[i]:.2f}ms)" for i in slow), flush=True)
    print("sweep " + " ".join(
        f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in row.items()), flush=True)
    return row


def descend(args, traf, ks, daemon, work) -> None:
    t_start = time.monotonic()
    step, held = 100, None
    for rate in args.descend:
        passed = 0
        for _ in range(args.windows):
            if time.monotonic() - t_start > args.budget_s:
                break
            step += 1
            row = one_rate(rate, args.seed + step, args.seconds, traf, ks, daemon, work)
            if row["collapsed"]:
                break
            passed += 1
        print(f"sweep descend rate={rate} windows_without_collapse={passed} "
              f"of {args.windows}", flush=True)
        if passed == args.windows:
            held = rate
            break
        if time.monotonic() - t_start > args.budget_s:
            break
    print(f"sweep descend highest_held={held} "
          f"four_fifths={None if held is None else 0.8 * held}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--start", type=float, default=25.0)
    ap.add_argument("--bisect", type=int, default=2)
    ap.add_argument("--rates", type=float, nargs="*", default=None,
                    help="diagnostic: just these rates, no search")
    ap.add_argument("--descend", type=float, nargs="*", default=None,
                    help="rates from the highest down: --windows windows in "
                    "a row at each, until one rate never collapses")
    ap.add_argument("--windows", type=int, default=6)
    ap.add_argument("--budget-s", type=float, default=3000.0,
                    help="--descend stops opening windows after this long")
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    args = ap.parse_args()
    m = manifest.load(ROOT)
    manifest.check(m, ROOT)
    cell = next(w for w in m["workloads"] if w["name"] == args.workload)
    conf = harness.load_json(os.path.join(ROOT, next(
        c["file"] for c in m["configs"] if c["name"] == cell["config"])))
    traf = harness.load_json(manifest.traffic_path(
        ROOT, manifest.bench_dir(m), cell["traffic"]))
    require(traf["loop"] == "open", "a sweep is for an open-loop cell")
    work = os.path.join(ROOT, harness.WORK, f"sweep-{cell['name']}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    print(f"sweep cell={cell['name']} seconds={args.seconds} "
          f"host_cores={os.cpu_count()}", flush=True)
    daemon = Daemon("daemon", conf, args.platform, int(cell["chips"]), ROOT, work)
    try:
        daemon.wait_healthy()
        dev = http_json(daemon.http_addr, "/debug/device")
        require(dev["platform"] == args.platform,
                f"the server runs on {dev['platform']!r}")
        print(f"device: platform={dev['platform']} kind={dev['device_kind']} "
              f"count={dev['device_count']}", flush=True)
        ks = traffic.Keyspace.from_config(conf, args.seed)
        rate, step = args.start, 1
        good, bad = None, None
        print("sweep warm-up (not a row of the table):", flush=True)
        one_rate(args.start, args.seed, min(args.seconds, 3.0), traf, ks, daemon, work)
        for step, r in enumerate(args.rates or [], start=1):
            one_rate(r, args.seed + step, args.seconds, traf, ks, daemon, work)
        if args.rates:
            daemon.stop()
            return 0
        if args.descend:
            descend(args, traf, ks, daemon, work)
            daemon.stop()
            return 0
        while bad is None and rate < 1e6:
            row = one_rate(rate, args.seed + step, args.seconds, traf, ks, daemon, work)
            step += 1
            if row["sustained"]:
                good, rate = rate, rate * 2
            else:
                bad = rate
        require(good is not None, f"the starting rate {args.start} is not sustained")
        for _ in range(args.bisect if bad is not None else 0):
            mid = (good + bad) / 2
            row = one_rate(mid, args.seed + step, args.seconds, traf, ks, daemon, work)
            step += 1
            if row["sustained"]:
                good = mid
            else:
                bad = mid
        print(f"sweep highest_sustained={good} first_not_sustained={bad} "
              f"four_fifths={0.8 * good}", flush=True)
        daemon.stop()
    except BenchFailure as e:
        print(f"SWEEP FAILURE: {e}", flush=True)
        return 1
    finally:
        daemon.child.kill()
    return 0


if __name__ == "__main__":
    sys.exit(main())
