#!/usr/bin/env python3
"""Sets of runs of one cell in one call, and their spread: what a
benchmark PR measures before it sets a bound.

    python benchmarks/proof.py --workload <cell> --seeds 11 12 13 14 15 16 --sets 2

Runs the cell once per seed, in each set the same seeds, each run a
process of its own (as the driver's are), and prints for every end-to-end
metric, and every reading an open-loop run prints beside them, each
set's median and spread: the distance between the first and the third
quartile (``statistics.quantiles(values, n=4)``) as a share of the median. ``--control <kind>`` runs control.py's relay instead and
expects every run to come out not correct.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ECHO = ("setup:", "open loop", "after the window", "check evicted_keys")


def one(cell: str, seed: int, seconds, trace: int, extra: list):
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", cell, "--seed", str(seed), "--trace", str(trace), *extra]
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    r = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    lines = r.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if r.returncode == 0 else None
    except (ValueError, IndexError):
        result = None
    if result is None or not result["correct"]:
        print("\n".join(lines[-30:]), flush=True)
    else:  # the readings beside the metrics: phases of set-up, the whole tail
        print("\n".join(ln for ln in lines if ln.startswith(ECHO)), flush=True)
        result["beside"] = {
            k: float(v) for ln in lines if ln.startswith("open loop:")
            for k, _, v in (tok.partition("=") for tok in ln.split()[2:]) if v}
    return r.returncode, result


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()
    extra = ["--platform", args.platform]
    if args.control:
        extra += ["--control", args.control]
    ok = True
    for s in range(args.sets):
        rows = []
        for seed in args.seeds:
            rc, res = one(args.workload, seed, args.seconds, args.trace, extra)
            if res is None:
                print(f"set {s} seed {seed}: rc={rc}, no result", flush=True)
                ok = False
                continue
            vals = {k: v["value"] for k, v in res["metrics"].items()}
            for k, v in res.get("beside", {}).items():
                vals.setdefault(k, v)
            print(f"set {s} seed {seed}: correct={res['correct']} "
                  f"attempted={res['attempted']} failed={res['failed']} "
                  + " ".join(f"{k}={v}" for k, v in vals.items()), flush=True)
            ok &= res["correct"] != bool(args.control)
            rows.append(vals)
        names = rows[0].keys() if rows else []
        for name in names:
            vs = [r[name] for r in rows if r.get(name) is not None]
            if len(vs) >= 2:
                print(f"set {s} {name}: n={len(vs)} median={statistics.median(vs)} "
                      f"spread={spread(vs):.5f} min={min(vs)} max={max(vs)}",
                      flush=True)
    print("proof " + ("ok" if ok else "FAILED"), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
