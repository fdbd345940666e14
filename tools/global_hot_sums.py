#!/usr/bin/env python
"""What a run of a GLOBAL cell over-admitted, counted twice: from the
answers the benchmark kept and from the program's own counter.

    python tools/global_hot_sums.py -- python3 benchmarks/run.py \
        --workload global-hot-4.herd-zipf --seed 7 --seconds 51 --trace 0

Runs the command and passes its output on. At two of its lines it
scrapes the daemon's `/metrics` (the address is in the daemon's log,
`.bench_out/<cell>-t<trace>/daemon.log`): at `setup: ...`, when the
copies have met after the set-up check and the window is about to open,
and at `quiesce: after the window`, when they have met again. Nothing
is scraped in between. When the command has ended it reads what the
comparison was given (`compared.pkl`, beside the log) and prints one
JSON line, `GLOBAL_SUMS {...}`, the counters as growth between the two
scrapes:

    over_limit_answers   OVER_LIMIT answers in the window (the work
                         `window.global_over_limit_before_used_up` had)
    used_up_keys         keys whose accepted hits reached what was left
    accepted_over_start  sum over them of accepted - what was left
    over_admitted_hits   gubernator_global_over_admitted_hits
    merged_hits          gubernator_global_merged_hits
    replica_decisions    gubernator_replica_decisions by device

`accepted_over_start` and `over_admitted_hits` have to be equal where no
call failed (`uncertain_keys` 0): every hit the replicas together took
beyond a limit is one the owner's bucket could no longer take, and the
program counted it. Exits with the command's code. Touches no chip and
imports no JAX.
"""

from __future__ import annotations

import json
import os
import pickle
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks.daemon import scrape  # noqa: E402
from benchmarks.reference.oracle import OVER_LIMIT, UNDER_LIMIT  # noqa: E402

MERGED = "gubernator_global_merged_hits"
OVER = "gubernator_global_over_admitted_hits"
BY_DEVICE = "gubernator_replica_decisions{device="
BEFORE, AFTER = "setup: ", "quiesce: after the window"


def arg_after(argv: list, flag: str, default: str) -> str:
    return argv[argv.index(flag) + 1] if flag in argv else default


def counters(work: str) -> dict:
    """The GLOBAL sync's counters as the run's daemon has them now."""
    with open(os.path.join(work, "daemon.log"), encoding="utf-8",
              errors="replace") as f:
        addr = re.search(r"listening: .*http=(\S+)", f.read()).group(1)
    return {k: v for k, v in scrape(addr).items()
            if k in (MERGED, OVER) or k.startswith(BY_DEVICE)}


def sums(work: str) -> dict:
    with open(os.path.join(work, "compared.pkl"), "rb") as f:
        kept = pickle.load(f)
    it, carried = kept["items"], kept["carried"]
    n = len(carried.remaining)
    took = it.valid & (it.status == UNDER_LIMIT)
    accepted = np.bincount(it.key[took], minlength=n)
    # what was left as the window opened (a key the run never preloaded
    # or checked starts full: its first answer says so; none here)
    start = np.where(carried.reset_time >= 0, carried.remaining, 0)
    seen = np.bincount(it.key[it.valid], minlength=n) > 0
    used_up = seen & (carried.reset_time >= 0) & (accepted >= start)
    return {
        "window_answers": int(np.sum(it.valid)),
        "over_limit_answers": int(np.sum(it.valid & (it.status == OVER_LIMIT))),
        "used_up_keys": int(np.sum(used_up)),
        "accepted_over_start": int(np.sum((accepted - start)[used_up])),
        "uncertain_keys": int(np.sum(kept["uncertain"])),
    }


def main() -> int:
    if "--" not in sys.argv:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    cmd = sys.argv[sys.argv.index("--") + 1:]
    cell = arg_after(cmd, "--workload", "")
    work = os.path.join(ROOT, ".bench_out",
                        f"{cell}-t{arg_after(cmd, '--trace', '0')}")
    at: dict = {}
    proc = subprocess.Popen(cmd, cwd=ROOT, stdout=subprocess.PIPE, text=True)
    for line in proc.stdout:
        sys.stdout.write(line)
        sys.stdout.flush()
        for mark in (BEFORE, AFTER):
            if line.startswith(mark):
                at[mark] = counters(work)
    rc = proc.wait()
    out = sums(work) if os.path.isfile(os.path.join(work, "compared.pkl")) else {}
    if BEFORE in at and AFTER in at:
        grown = {k: v - at[BEFORE].get(k, 0.0) for k, v in at[AFTER].items()}
        out["over_admitted_hits"] = grown.get(OVER)
        out["merged_hits"] = grown.get(MERGED)
        out["replica_decisions"] = [
            grown[k] for k in sorted(grown) if k.startswith(BY_DEVICE)]
    print("GLOBAL_SUMS " + json.dumps(out), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
