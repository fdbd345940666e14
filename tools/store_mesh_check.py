#!/usr/bin/env python
"""A Store on the owner-sharded table against the plain reference, at the
configuration's own size, in one process (ISSUE 41; not a benchmark cell).

    python tools/store_mesh_check.py --seed <n> [--calls 2000] [--passes 2]
        [--config store-4] [--path columnar|object] [--keys <n> --rehearsal]

Builds the configuration's engine from its `env` exactly as the daemon
does (`setup_daemon_config`: an IciEngine over every device the process
sees, a DeviceEngine where the configuration is a one-chip one), attaches
a MemoryStore, sends the first `--calls` calls of the cell's `calls100`
plan for `--seed` through the columnar path (`check_columns` on the
encoded request, what the gRPC fast edge calls; `--path object`: the
pump, `check_batch` on request objects, what the HTTP gateway and a call
over `max_waves` take), and compares:

- every answer with `benchmarks/reference/oracle.py`'s, which has no
  capacity and no Store;
- every entry of the Store with a plain model of what a Store must hold:
  per key the reference's bucket after the key's last request (a token
  bucket's RESET_REMAINING removes the entry);
- `on_change_items + removes` of each call with its distinct keys;
- `gubernator_store_rows_skipped` and
  `gubernator_engine_store_stacked_surprises`, which have to stay 0.

The first pass over the calls meets its keys for the first time, so
nearly every flush knows it reads through and runs the per-wave
sequence; `--passes 2` sends the same calls again, their keys resident
now, so that the flushes run stacked (docs/persistence.md "When the
waves run stacked"; ISSUE 45). Prints one JSON object: entries,
mismatches, skipped rows, the flushes by sequence, the device it ran
on. Exit code 0 only if everything agrees. `--rehearsal` applies the
configuration's `rehearsal_env` (a small table) for a run on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def reference_entry(item) -> tuple:
    """(algorithm, status, limit, duration, remaining, stamp, expire_at,
    burst) of one of the reference's cache entries."""
    v = item.value
    if item.algorithm == 0:  # token bucket
        return (0, v.status, v.limit, v.duration, v.remaining, v.created_at,
                item.expire_at, 0)
    return (1, 0, v.limit, v.duration, v.remaining_s, v.updated_at,
            item.expire_at, v.burst)


def store_entry(snap) -> tuple:
    token = int(snap.algorithm) == 0
    return (int(snap.algorithm), int(snap.status) if token else 0, snap.limit,
            snap.duration, snap.remaining, snap.stamp, snap.expire_at,
            0 if token else snap.burst)


def total(counter) -> float:
    """The sum over a _BareCounter's exposed children."""
    return sum(float(ln.rpartition(" ")[2]) for ln in counter.render_lines()
               if not ln.startswith("#"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=2000)
    ap.add_argument("--passes", type=int, default=1)
    ap.add_argument("--config", default="store-4")
    ap.add_argument("--traffic", default="calls100")
    ap.add_argument("--path", choices=("columnar", "object"), default="columnar")
    ap.add_argument("--keys", type=int, default=None)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    with open(os.path.join(ROOT, "benchmarks/configs", args.config + ".json"),
              encoding="utf-8") as f:
        conf = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/traffic", args.traffic + ".json"),
              encoding="utf-8") as f:
        traf = json.load(f)
    env = dict(conf["env"])
    if args.rehearsal:
        env.update(conf.get("rehearsal_env", {}))
    if args.keys is not None:
        conf["keyspace"]["keys"] = args.keys
    os.environ.update(env)

    from benchmarks import traffic
    from benchmarks.reference.oracle import Reference

    ks = traffic.Keyspace.from_config(conf, args.seed)
    plan = traffic.build_plan(traf, ks, args.seed, 1.0)
    n_calls = min(args.calls, len(plan.blobs))

    import jax

    from gubernator_tpu import wire
    from gubernator_tpu.service.envconfig import setup_daemon_config
    from gubernator_tpu.store import MemoryStore, attach_store

    from gubernator_tpu.utils.compilecache import enable_compile_cache

    enable_compile_cache()  # as cmd.daemon does: a daemon's programs are found again
    dconf = setup_daemon_config(None)
    t0 = time.monotonic()
    if dconf.global_mode == "ici":
        from gubernator_tpu.runtime.ici_engine import IciEngine

        eng = IciEngine(dconf.ici)
    else:
        from gubernator_tpu.runtime.engine import DeviceEngine

        eng = DeviceEngine(dconf.engine_config())
    store = MemoryStore()
    attach_store(eng, store)
    start_s = time.monotonic() - t0
    em = eng.metrics

    def handed() -> float:
        return total(em.store_on_change_items) + total(em.store_removes)

    ref = Reference()
    wrong_answers = wrong_handed = 0
    first = None
    t0 = time.monotonic()
    try:
        for i in list(range(n_calls)) * args.passes:
            now = eng.now_fn()
            before = handed()
            cols = wire.parse_requests(plan.blobs[i])
            if args.path == "columnar":
                got = eng.check_columns(cols, now=now)
                if got is None:
                    raise SystemExit(f"call {i} left the columnar path")
                got = zip(*(a.tolist() for a in got))
            else:
                reqs = [wire.req_from_columns(cols, j) for j in range(cols.n)]
                for r in reqs:
                    r.created_at = now
                got = [(int(r.status), r.limit, r.remaining, r.reset_time)
                       for r in eng.check_batch(reqs)]
            ids = plan.keys[i]
            want = [ref.decide(ks.request(int(k), int(traf.get("hits", 1)),
                                          created_at=now,
                                          behavior=int(b)), now).as_tuple()[:4]
                    for k, b in zip(ids, plan.behaviors[i])]
            bad = sum(g != w for g, w in zip(got, want))
            wrong_answers += bad
            if bad and first is None:
                first = f"call {i}: {bad} answers differ from the reference's"
            wrong_handed += handed() - before != len(set(ids.tolist()))
        run_s = time.monotonic() - t0
        counter = getattr(em, "store_rows_skipped", None)  # PR 41's
        skipped = None if counter is None else total(counter)
        sequences = surprises = None
        if hasattr(em, "store_flushes"):  # PR 45's
            sequences = {q: em.store_flushes.labels(q).get()
                         for q in ("stacked", "per_wave")}
            surprises = total(em.store_stacked_surprises)
        got = {k: store_entry(s) for k, s in store.data.items()}
        want = {k: reference_entry(v) for k, v in ref.cache.items()}
        mismatches = sum(got.get(k) != w for k, w in want.items())
        mismatches += len(set(got) - set(want))
        if mismatches and first is None:
            k = next(k for k in want if got.get(k) != want[k])
            first = f"{k}: store {got.get(k)} reference {want[k]}"
        dev = jax.devices()[0]
        result = {
            "config": args.config, "seed": args.seed, "path": args.path,
            "calls": n_calls, "passes": args.passes,
            "items": args.passes * int(
                sum(len(plan.keys[i]) for i in range(n_calls))),
            "entries": len(got), "reference_entries": len(want),
            "mismatches": mismatches, "skipped_rows": skipped,
            "store_flushes": sequences, "stacked_surprises": surprises,
            "wrong_answers": wrong_answers,
            "calls_not_handing_their_distinct_keys": wrong_handed,
            "first": first, "start_s": start_s, "run_s": run_s,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "engine_devices": len(eng.devices)},
        }
    finally:
        eng.close()
    print(json.dumps(result))
    ok = not (mismatches or skipped or surprises or wrong_answers
              or wrong_handed)
    return 0 if ok and len(got) > 0 else 1


if __name__ == "__main__":
    sys.exit(main())
