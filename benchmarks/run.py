#!/usr/bin/env python3
"""One run of one cell: ``GetRateLimits`` from the client's side of the
socket.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX. It starts the configuration's server
through the normal entry point, makes the cell's traffic from ``--seed``
while the server starts, preloads, checks the answers against the plain
reference with a pinned clock, opens the window with JAX-free load
generators, probes what is left, stops the server, and prints one JSON
object as the last line of stdout. Everything that belongs to one
configuration, traffic mix or per-layer metric is a file found by the
name in ``BENCHMARK.json``.

A configuration whose file says ``"preload": {"via": "snapshot"}`` is not
preloaded over gRPC: the pinned clock is fixed and the reference's state
after the preload's own request written as a checkpoint (snapshot.py:
key, algorithm, expire-at, status, limit, duration, remaining, created-at)
*before* the server starts, and the server's launcher is told where it is
(``BENCH_SNAPSHOT_IN``). ``start_s`` then holds the Load and ``preload_s``
the making and writing of the file. With ``"shutdown": {"saved":
"checked"}`` the launcher is also told where to save at shutdown
(``BENCH_SNAPSHOT_OUT``); after the probes the run waits for the exit,
prints ``save_s`` (SIGTERM to exit) and holds the file to what the probes
answered (check.py, stage 4). Both paths lie in the run's own directory.

Without a TPU the run exits non-zero with no result line.
``--platform cpu`` is the rehearsal: its result carries ``correct`` and
counts, and null for every time, rate and share.
"""

from __future__ import annotations

import time

T_EXEC = time.monotonic()  # set-up is counted from here to the window's opening

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import urllib.request  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks import (  # noqa: E402
    check,
    consistency,
    control,
    manifest,
    readers,
    snapshot,
    stats,
    traffic,
    wire,
)
from benchmarks.reference.oracle import Request  # noqa: E402
from benchmarks.daemon import (  # noqa: E402
    BenchFailure,
    Child,
    Daemon,
    free_port,
    http_json,
    require,
    scrape,
)

WORK = ".bench_out"  # git-ignored; one sub-directory per run
# A call's deadline unless the traffic file gives `call_deadline_s`. ISSUE 23
# asked for 5 s; the check of PR 23 met a freeze that failed all 100 of
# `herd`'s calls in flight at once in one run of twelve. Far above any
# freeze seen (8 s), a freeze costs a run time, which its rate or tail
# shows, and a call still fails where the server never answers it.
CALL_DEADLINE_S = 30.0
SETUP_CHECK_CALLS = 200
P99_MIN_CALLS = 1000
PRELOAD_ITEMS = 1000
PRELOAD_IN_FLIGHT = 8
PROBES_IN_FLIGHT = 4
LEAD_S = 0.75  # from GO to the window's opening: every worker is waiting by then
# A traced run, per chip of the cell: what /debug/profile may take beyond the
# capture itself (stop_trace decodes one device plane a chip), and how long
# after the window the capture's thread is waited for.
PROFILE_STOP_S = 90.0
HOOK_JOIN_S = 120.0


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


# ---- calls the parent makes itself: preload, set-up check, probes --------------


class Client:
    def __init__(self, target: str):
        self.channel, self.stub = wire.open_channel(target)

    def send(self, reqs) -> list:
        return wire.decode_call(self.stub(wire.encode_call(reqs), timeout=120))

    def close(self) -> None:
        self.channel.close()


def preload_blobs(ks, spec: dict, t_pin: int) -> list:
    """Every key of the configuration in encoded calls of 1,000, pinned
    clock. Made on a thread while the server starts: the parent is idle then."""
    hits = int(spec.get("hits", 1))
    return [
        wire.encode_call([ks.request(k, hits, created_at=t_pin)
                          for k in range(off, min(off + PRELOAD_ITEMS, ks.n))])
        for off in range(0, ks.n, PRELOAD_ITEMS)
    ]


def preload(client: Client, ks, spec: dict, t_pin: int, blobs: list) -> None:
    """Sends the preload, a few calls in flight at once; every answer
    equals the reference's first answer for a new key."""
    hits = int(spec.get("hits", 1))
    new_token = (0, ks.limit, ks.limit - hits, t_pin + ks.duration_ms, "")

    def one(n: int) -> int:
        off = n * PRELOAD_ITEMS
        ids = range(off, min(off + PRELOAD_ITEMS, ks.n))
        got = wire.decode_call(client.stub(blobs[n], timeout=120))
        require(len(got) == len(ids), "preload: short response")
        bad = 0
        for k, g in zip(ids, got):
            if ks.algorithm_of(k) == wire.TOKEN_BUCKET:
                bad += g != new_token
            else:
                bad += g[:3] != new_token[:3] or g[4] != ""
        return bad

    with ThreadPoolExecutor(max_workers=PRELOAD_IN_FLIGHT) as pool:
        bad = sum(pool.map(one, range(len(blobs))))
    require(bad == 0, f"preload: {bad} answers differ from a new key's")


def setup_check(seq: check.Sequential, ks, plan, t_chk: int,
                n_calls: int, max_wall_s: float, settle=None) -> int:
    """Stage 1: calls of the cell's own mix (its keys, hits and flags), one
    at a time, pinned clock advancing 10 ms a call, each answer equal to the
    reference's. Stops early if wall time nears the shortest bucket's life
    (the server's expiry runs on its own clock). With `settle` (a guarantee
    that is eventual: it blocks until every copy holds what was sent) the first
    `n_calls / 2` calls whose keys differ are each sent twice, the list
    once and then again, and a call that names a key sent since the copies
    last met waits for `settle` first: the second answer has to show the
    first's hit, whichever copy gives it. Returns the calls made."""
    t_start = time.monotonic()
    chosen = range(len(plan.keys))
    if settle:  # a call that names a key twice: two copies may answer its items
        chosen = [i for i in chosen
                  if len(set(plan.keys[i].tolist())) == len(plan.keys[i])]
        chosen = chosen[:n_calls // 2] * 2
    unsettled: set = set()
    made = 0
    for n, i in enumerate(chosen):
        if made == n_calls or time.monotonic() - t_start > max_wall_s:
            break
        now = t_chk + 10 * (n + 1)
        ids, behs, hits = plan.keys[i], plan.behaviors[i], plan.hits[i]
        if settle and unsettled & set(ids.tolist()):
            settle()
            unsettled.clear()
        reqs = [ks.request(k, int(h), created_at=now, behavior=int(b))
                for k, h, b in zip(ids, hits, behs)]
        seq.call(f"setup-check call {i}", ids, reqs, now)
        unsettled.update(ids.tolist())
        made += 1
    return made


def probe_keys(ks, conf: dict, traf: dict, seed: int) -> np.ndarray:
    """The configuration's `probes`: the traffic's hottest keys and a
    sample drawn from the seed."""
    spec = conf.get("probes", {})
    hot = traffic.hottest_keys(traf.get("keys", {}), ks.n, int(spec.get("hottest", 0)))
    seeded = traffic.rng_for(seed, 9).choice(
        ks.n, size=min(int(spec.get("seeded", ks.n)), ks.n), replace=False)
    return np.unique(np.concatenate([hot, seeded]))


def recent_keys(plan, res: dict, seconds: float, recent_s: float) -> np.ndarray:
    """The configuration's `probes.recent_s`: the keys of the calls the
    window sent in its last `recent_s` seconds, whose buckets still live
    where a bucket's life is short."""
    late = res["call"][res["sent"] >= seconds - recent_s]
    return np.unique(np.concatenate(
        [plan.keys[int(i)] for i in late] or [np.zeros(0, np.int64)]))


def send_probes(client: Client, ks, ids: np.ndarray) -> check.Items:
    """Stage 3: ``hits=0`` looks at the keys `ids`, in that order."""
    parts = [ids[off:off + PRELOAD_ITEMS] for off in range(0, len(ids), PRELOAD_ITEMS)]
    with ThreadPoolExecutor(max_workers=PROBES_IN_FLIGHT) as pool:
        answers = list(pool.map(
            lambda part: client.send([ks.request(k, 0) for k in part]), parts))
    require(all(len(a) == len(p) for a, p in zip(answers, parts)),
            "probe: short response")
    got = [g for a in answers for g in a]
    p = np.asarray([g[:4] for g in got], dtype=np.int64).reshape(-1, 4)
    return check.Items(
        key=ids, status=p[:, 0], limit=p[:, 1], remaining=p[:, 2], reset_time=p[:, 3],
        valid=np.asarray([g[4] == "" for g in got], dtype=bool),
        behavior=np.full(len(ids), ks.behavior),
        hits=np.zeros(len(ids), dtype=np.int64),
    )


# ---- workers ----------------------------------------------------------------------


def call_deadline_s(traf: dict) -> float:
    return float(traf.get("call_deadline_s", CALL_DEADLINE_S))


def warmup_request(w: int) -> Request:
    """Worker w's first call, a look at a key of its own outside the
    keyspace: its channel is up before the window."""
    return Request(name="bench-warmup", unique_key=f"worker{w}", hits=0,
                   limit=1, duration=60_000)


def start_workers(plan, targets, n_workers: int, work: str,
                  deadline_s: float) -> list:
    workers = []
    for w in range(n_workers):
        job = {"loop": plan.loop, "targets": targets, "blobs": {},
               "deadline_s": deadline_s,
               "warmup": wire.encode_call([warmup_request(w)])}
        if plan.loop == "closed":
            pools = {}
            for c in range(w, plan.callers, n_workers):
                pools[c] = np.nonzero(plan.caller_of == c)[0].tolist()
            job["pools"] = pools
            mine = [i for pool in pools.values() for i in pool]
        else:
            mine = list(range(w, len(plan.due), n_workers))
            job["calls"] = mine
            job["due"] = [float(plan.due[i]) for i in mine]
        job["blobs"] = {i: plan.blobs[i] for i in mine}
        job_path = os.path.join(work, f"worker{w}.pkl")
        with open(job_path, "wb") as f:
            pickle.dump(job, f)
        env = {k: v for k, v in os.environ.items() if not k.startswith("GUBER_")}
        child = Child(
            f"worker{w}",
            [sys.executable, os.path.join(ROOT, "benchmarks", "loadgen.py"),
             job_path, os.path.join(work, f"worker{w}.npz")],
            env, ROOT, work, stdin=subprocess.PIPE, stdout_pipe=True,
        )
        workers.append(child)
    return workers


def workers_ready(workers) -> None:
    for w in workers:
        line = w.proc.stdout.readline().decode().strip()
        require(line == "READY", f"{w.label} said {line!r}: {w.log_text()[-2000:]}")


def run_window(workers, seconds: float, mid_hook=None, chips: int = 1) -> float:
    """GO to every worker; returns the window's opening (monotonic)."""
    t0 = time.monotonic() + LEAD_S
    for w in workers:
        w.proc.stdin.write(f"GO {t0!r} {seconds!r}\n".encode())
        w.proc.stdin.flush()
    hook = None
    if mid_hook is not None:
        hook = threading.Thread(target=mid_hook, args=(t0,), daemon=True)
        hook.start()
    for w in workers:
        line = w.proc.stdout.readline().decode().strip()
        require(line.startswith("DONE"),
                f"{w.label} said {line!r}: {w.log_text()[-2000:]}")
        require(w.proc.wait(timeout=60) == 0, f"{w.label} exited non-zero")
    if hook is not None:
        hook.join(timeout=HOOK_JOIN_S * chips)
    return t0


def gather(workers_n: int, work: str, plan) -> dict:
    """Workers' outputs as flat per-call and per-item arrays."""
    # dict(): every array read once (an NpzFile reads its file anew on each access)
    parts = [dict(np.load(os.path.join(work, f"worker{w}.npz")))
             for w in range(workers_n)]
    call = np.concatenate([p["call"] for p in parts])
    out = {k: np.concatenate([p[k] for p in parts])
           for k in ("due", "sent", "done", "ok")}
    out["call"] = call
    sizes = np.array([len(plan.keys[i]) for i in call], dtype=np.int64)
    got = np.concatenate([np.diff(p["offsets"]) for p in parts])
    # a call that returned fewer answers than it asked is a failed call
    out["ok"] = out["ok"] & (got == sizes)
    out["sizes"] = sizes
    key, beh, hits, valid = [], [], [], []
    cols = {k: [] for k in ("status", "limit", "remaining", "reset_time")}
    n = 0
    for p in parts:
        offs = p["offsets"]
        for j in range(len(p["call"])):
            i = int(p["call"][j])
            ok = bool(out["ok"][n])
            n += 1
            if not ok:
                continue
            a, b = int(offs[j]), int(offs[j + 1])
            key.append(plan.keys[i])
            beh.append(plan.behaviors[i])
            hits.append(plan.hits[i])
            valid.append(~p["item_error"][a:b])
            for c in cols:
                cols[c].append(p[c][a:b])
    first_error = next((str(p["first_error"]) for p in parts
                        if str(p["first_error"])), "")
    codes, counts = np.unique(np.concatenate([p["rpc_error"] for p in parts]),
                              return_counts=True)
    out["rpc_errors"] = {str(c): int(n) for c, n in zip(codes, counts)}

    def cat(xs, dtype):
        return np.concatenate(xs) if xs else np.zeros(0, dtype)

    out["items"] = check.Items(
        key=cat(key, np.int64), status=cat(cols["status"], np.int64),
        limit=cat(cols["limit"], np.int64),
        remaining=cat(cols["remaining"], np.int64),
        reset_time=cat(cols["reset_time"], np.int64),
        valid=cat(valid, bool), behavior=cat(beh, np.int64),
        hits=cat(hits, np.int64),
    )
    out["first_error"] = first_error
    return out


def open_loop_readings(res: dict, which: np.ndarray, deadline_s: float) -> dict:
    """The generator's own clock over the calls `which`: latency from when
    a call was due (a failed call counts at the deadline), and how late the
    generator sent it."""
    lat = np.where(res["ok"], (res["done"] - res["due"]) * 1000.0,
                   deadline_s * 1000.0)[which]
    late = ((res["sent"] - res["due"]) * 1000.0)[which]
    out = {f"call_p{q}_ms": stats.percentile(lat, q) for q in (50, 90, 95, 99)}
    out["call_mean_ms"] = float(lat.mean())
    out["call_max_ms"] = float(lat.max())
    out["late_p99_ms"] = stats.percentile(late, 99)
    out["late_max_ms"] = float(late.max())
    return out


# ---- the run -------------------------------------------------------------------------


def device_object(dev: dict) -> dict:
    rows = dev.get("memory", {}).get("devices", [])
    peaks = [r["peak_bytes_in_use"] for r in rows
             if r.get("peak_bytes_in_use") is not None]
    return {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"],
        "memory_peak_bytes": max(peaks) if peaks else None,
    }


def capture_trace(http_addr: str, seconds: float, at_s: float, out: dict,
                  chips: int = 1):
    """A traced run's readings, all inside the window. One second in:
    scrape ``/metrics``. Mid-window: scrape again, then ask for the
    profile. The per-layer deltas lie between the two scrapes: both see the
    same calls in flight, and neither the profiler's start nor its stop,
    which stall the host, falls between them."""
    def hook(t0: float) -> None:
        try:
            time.sleep(max(t0 + min(1.0, at_s / 2) - time.monotonic(), 0))
            out["scrape0"] = scrape(http_addr)
            out["scrape0_at_s"] = time.monotonic() - t0
            time.sleep(max(t0 + at_s - time.monotonic(), 0))
            out["scrape"] = scrape(http_addr)
            out["scrape_at_s"] = time.monotonic() - t0
            with urllib.request.urlopen(
                f"http://{http_addr}/debug/profile?seconds={seconds}",
                timeout=seconds + PROFILE_STOP_S * chips,
            ) as r:
                out.update(json.loads(r.read()))
        except Exception as e:  # reported by the caller: a traced run needs it
            out["error"] = repr(e)
    return hook


def reduce_trace(trace_dir: str, work: str) -> dict:
    """In a helper process pinned to the CPU backend: it never touches the
    chip, and this process never imports JAX."""
    out_path = os.path.join(work, "trace.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "trace_reduce.py"),
         trace_dir, out_path],
        env=env, capture_output=True, text=True, timeout=300,
    )
    require(r.returncode == 0, f"trace reduction failed:\n{r.stderr[-3000:]}")
    return load_json(out_path)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="cpu is the explicit rehearsal; nothing selects it "
                    "implicitly and it reports no time, rate or share")
    ap.add_argument("--control", default=None,
                    help="not a benchmark run: put control.py's relay, which "
                    "breaks one guarantee, under the timed path, or break a "
                    "snapshot (stale_snapshot, drop_saved); `correct` has to "
                    "come out false")
    ap.add_argument("--keys", type=int, default=None,
                    help="rehearsal only: a smaller keyspace")
    args = ap.parse_args()
    try:
        return run(args)
    except BenchFailure as e:
        say(f"BENCH FAILURE: {e}")
        return 1


def run(args) -> int:
    m = manifest.load(ROOT)
    try:
        manifest.check(m, ROOT)
    except manifest.ManifestError as e:
        raise BenchFailure(f"BENCHMARK.json: {e}")
    cells = {w["name"]: w for w in m["workloads"]}
    require(args.workload in cells, f"no cell {args.workload!r}")
    cell = cells[args.workload]
    base = manifest.bench_dir(m)
    conf_entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    conf = load_json(os.path.join(ROOT, conf_entry["file"]))
    traf = load_json(manifest.traffic_path(ROOT, base, cell["traffic"]))
    seconds = float(args.seconds if args.seconds is not None else m["run_seconds"])
    require(args.keys is None or args.platform == "cpu",
            "--keys is for the --platform cpu rehearsal only")
    require(os.path.isdir(os.path.join(ROOT, "gubernator_tpu")),
            "the system under test (gubernator_tpu/) is not in this checkout")
    if args.keys is not None:
        conf["keyspace"]["keys"] = args.keys
        conf["env"].update(conf.get("rehearsal_env", {}))
        traf.update(traf.get("rehearsal", {}))
    chips = int(cell["chips"])
    say(f"cell={cell['name']} config={cell['config']} traffic={cell['traffic']} "
        f"chips={chips} seed={args.seed} seconds={seconds} trace={args.trace} "
        f"host_cores={os.cpu_count()}")

    work = os.path.join(ROOT, WORK, f"{cell['name']}-t{args.trace}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    daemons, workers = [], []
    try:
        return measure(args, m, cell, conf, traf, seconds, chips, work,
                       daemons, workers)
    finally:
        for c in workers:
            c.kill()
        for d in daemons:
            d.child.kill()


def measure(args, m, cell, conf, traf, seconds, chips, work,
            daemons, workers) -> int:
    ks = traffic.Keyspace.from_config(conf, args.seed)
    spec = conf.get("preload")
    by_snapshot = bool(spec) and spec.get("via", "grpc") == "snapshot"
    saved_path = (os.path.join(work, "snapshot_out.npz")
                  if conf.get("shutdown") else None)
    require(args.control != "stale_snapshot" or by_snapshot,
            "--control stale_snapshot: the configuration loads no snapshot")
    require(args.control != "drop_saved" or saved_path,
            "--control drop_saved: the configuration checks no saved snapshot")
    t_pin = int(time.time() * 1000)
    snapshot_env = {"BENCH_SNAPSHOT_OUT": saved_path} if saved_path else {}
    preload_s = 0.0
    t_preload = time.monotonic()
    hash_keys = snapshot.hash_keys(ks) if by_snapshot or saved_path else None
    if by_snapshot:  # the checkpoint is there before the server starts
        rows = snapshot.preload_rows(ks, int(spec.get("hits", 1)), t_pin)
        if args.control == "stale_snapshot":
            control.stale_snapshot(rows)
        snapshot_env["BENCH_SNAPSHOT_IN"] = os.path.join(work, "snapshot_in.npz")
        snapshot.write(snapshot_env["BENCH_SNAPSHOT_IN"], hash_keys, rows)
        preload_s = time.monotonic() - t_preload
    daemon = Daemon("daemon", conf, args.platform, chips, ROOT, work, snapshot_env)
    daemons.append(daemon)

    # the traffic and a gRPC preload are made while the server starts
    blobs_made = ThreadPoolExecutor(max_workers=1)
    blobs = (blobs_made.submit(preload_blobs, ks, spec, t_pin)
             if spec and not by_snapshot else None)
    plan = traffic.build_plan(traf, ks, args.seed, seconds)
    n_workers = int(traf.get("workers", 1))
    say(f"plan: loop={plan.loop} calls_made={len(plan.blobs)} "
        f"items_made={sum(len(k) for k in plan.keys)} workers={n_workers}")

    start_s = daemon.wait_healthy()
    dev = http_json(daemon.http_addr, "/debug/device")
    say(f"device: platform={dev['platform']} kind={dev['device_kind']} "
        f"count={dev['device_count']} memory_source={dev['memory']['source']}")
    require(dev["platform"] == args.platform,
            f"the server runs on platform={dev['platform']!r}, not "
            f"{args.platform!r}: JAX found no accelerator")
    if args.platform == "tpu":
        require(dev["device_count"] == chips,
                f"the server sees {dev['device_count']} chip(s), the cell "
                f"asks for {chips}")
        require(dev["memory"]["source"] == "device",
                "memory.source is not the device allocator")
        require(len(dev["memory"]["devices"]) == chips,
                f"the engine spans {len(dev['memory']['devices'])} chip(s), "
                f"the cell asks for {chips}")
    table = http_json(daemon.http_addr, "/debug/table")
    eventual = None
    geom = table
    if conf.get("consistency"):
        eventual = consistency.Eventual(conf["consistency"], daemon.http_addr)
        tier = eventual.table_tier
        if tier:  # the tier that holds this keyspace, e.g. 4-way replicas
            require(tier in table.get("tiers", {}),
                    f"/debug/table has no tier {tier!r}")
            geom = table["tiers"][tier]
            say(f"consistency: eventual; the keys live in tier {tier!r}")
    shape = (ks.n, geom["groups"], geom["ways"])
    say(f"table: groups={geom['groups']} ways={geom['ways']} "
        f"slots={geom['slots']} keys={ks.n} "
        f"lost_share={check.lost_share(*shape):.6f} "
        f"evictable_share={check.evictable_share(*shape):.6f}")

    target = daemon.grpc_addr
    if args.control in control.SNAPSHOT_KINDS:
        say(f"CONTROL RUN: {args.control}; not a benchmark run")
    elif args.control:
        port = free_port()
        relay = Child(
            "control",
            [sys.executable, os.path.join(ROOT, "benchmarks", "control.py"),
             args.control, str(port), daemon.grpc_addr],
            dict(os.environ), ROOT, work, stdout_pipe=True,
        )
        workers.append(relay)  # killed with the workers
        line = relay.proc.stdout.readline().decode().strip()
        require(line == "READY", f"control said {line!r}: {relay.log_text()[-2000:]}")
        target = f"127.0.0.1:{port}"
        say(f"CONTROL RUN: {args.control} relay under the timed path; not a benchmark run")
    deadline_s = call_deadline_s(traf)
    load_workers = start_workers(plan, [target], n_workers, work, deadline_s)
    workers.extend(load_workers)
    client = Client(daemon.grpc_addr)
    if blobs:
        t_preload = time.monotonic()
        preload(client, ks, spec, t_pin, blobs.result())
        preload_s = time.monotonic() - t_preload
    blobs_made.shutdown()
    hits0 = int(spec.get("hits", 1)) if spec else 0
    if eventual and spec:
        eventual.wait("after the preload")

    def history(key_id):
        return [(ks.request(key_id, hits0, created_at=t_pin), t_pin)] if spec else []

    seq = check.Sequential(client.send, history)
    t_check = time.monotonic()
    asked = int(traf.get("setup_check_calls", SETUP_CHECK_CALLS))
    if eventual:
        asked = eventual.setup_check_calls(asked)
    asked = min(asked, len(plan.keys))
    made = setup_check(
        seq, ks, plan, int(time.time() * 1000), asked,
        max_wall_s=0.4 * ks.duration_ms / 1000.0,
        settle=(lambda: eventual.wait("in the set-up check")) if eventual else None)
    if eventual:  # what is carried into the window is on every copy
        eventual.wait("after the set-up check")
    check_s = time.monotonic() - t_check
    say(f"setup: start_s={start_s:.3f} preload_s={preload_s:.3f} "
        f"check_s={check_s:.3f} check_calls={made} check_items={seq.items}")
    phases = {"start_s": start_s, "preload_s": preload_s, "check_s": check_s}
    if eventual:
        phases["quiesce_s"] = eventual.waited_s
        say(f"quiesce: before the window {eventual.waited_s:.3f} s in "
            f"{eventual.waits} waits (after a preload, the rest inside check_s)")

    # what the reference holds for the token keys as the window opens
    carried = check.Carried.empty(ks.n)
    if spec:
        tok = ks.is_token(np.arange(ks.n))
        carried.remaining[tok] = ks.limit - hits0
        carried.reset_time[tok] = t_pin + ks.duration_ms
    for k in seq.known:
        st = seq.token_state(k, ks)
        if st is None:  # a leaky key, or one whose bucket the check's last call removed
            st = (0, -1, False)
        carried.remaining[k], carried.reset_time[k], carried.sticky_over[k] = st

    workers_ready(load_workers)
    before = scrape(daemon.http_addr)
    trace_out: dict = {}
    hook = None
    if args.trace:
        t_trace = float(traf.get("trace_seconds", 3.0))
        t_trace = min(t_trace, max(seconds - 1.0, 0.2))
        hook = capture_trace(daemon.http_addr, t_trace,
                             (seconds - t_trace) / 2, trace_out, chips)
    born_lo = int(time.time() * 1000)
    t0 = run_window(load_workers, seconds, hook, chips)
    setup_s = t0 - T_EXEC
    t_closed = time.monotonic()
    born = (born_lo, int(time.time() * 1000))  # a bucket made in the window
    after = scrape(daemon.http_addr)
    dev_after = http_json(daemon.http_addr, "/debug/device")
    daemon.child.require_running()

    # stage 3: probes; then the server drains while the answers are compared
    ids = probe_keys(ks, conf, traf, args.seed)
    res = None
    if eventual:
        recent_s = conf.get("probes", {}).get("recent_s")
        blocks = [ids]
        if recent_s:  # before the rest, while their buckets live
            res = gather(n_workers, work, plan)
            recent = recent_keys(plan, res, seconds, float(recent_s))
            blocks = [recent, np.setdiff1d(ids, recent)]
            ids = np.concatenate(blocks)
        say(f"quiesce: after the window "
            f"{eventual.wait('after the window'):.3f} s (outside every metric)")
        # any copy may answer a probe: each key is asked `probe_repeats`
        # times, at consecutive places of one call
        probes = [send_probes(client, ks, np.repeat(block, eventual.probe_repeats))
                  for block in blocks if len(block)]
    else:
        probes = send_probes(client, ks, ids)
    client.close()
    t_probed = time.monotonic()
    stopping = ThreadPoolExecutor(max_workers=1)
    stopped = stopping.submit(daemon.stop)

    if res is None:
        res = gather(n_workers, work, plan)
    items = res["items"]
    failed_call = ~res["ok"]
    uncertain = np.zeros(ks.n, dtype=bool)
    for i in res["call"][failed_call]:
        uncertain[plan.keys[int(i)]] = True
    uncertain[items.key[~items.valid]] = True

    # what the comparison is given, kept beside the run's logs: a run that
    # came out not correct can be compared again without the chip
    with open(os.path.join(work, "compared.pkl"), "wb") as f:
        pickle.dump({"items": items, "probes": probes, "carried": carried,
                     "uncertain": uncertain, "born": born, "calls": res["call"],
                     "evicted_in_setup": sorted(seq.evicted)}, f)

    verdict = check.Verdict()
    if by_snapshot:
        check.check_load(geom, ks.n, verdict)
    verdict.add("setup.mismatches", seq.mismatches, 0, " | ".join(seq.examples))
    verdict.add("setup.calls_short", max(min(50, asked) - made, 0), 0)
    wc = check.WindowCheck(ks, carried, uncertain)
    wc.evicted.update(seq.evicted)
    wc.check_window(items, verdict)
    if eventual:
        # every hit the window sent to a key, failed calls included
        calls = [int(i) for i in res["call"]]
        none = [np.zeros(0, np.int64)]
        sent = np.bincount(
            np.concatenate([plan.keys[i] for i in calls] or none),
            weights=np.concatenate([plan.hits[i] for i in calls] or none),
            minlength=ks.n).astype(np.int64)
        wc.check_window_eventual(items, sent, born, eventual.join_ms, verdict)
        wc.check_probes_eventual(probes, verdict)
        say(f"eventual: {wc.joined} generations joined to a bucket made a moment "
            f"before on another copy; {wc.held_exact} probed keys held to "
            f"their totals on every copy")
    else:
        wc.check_probes(probes, verdict)
    observed = len(np.unique(np.concatenate([items.key, ids])))
    say(f"observed keys: {observed} of {ks.n}")
    wc.check_evictions(observed, geom["groups"], geom["ways"], verdict)
    cold = after.get("gubernator_engine_cold_compile_count", 0) - before.get(
        "gubernator_engine_cold_compile_count", 0)
    verdict.add("window.cold_compiles", cold, 0)
    if saved_path:  # stage 4, once the server has gone: what its Loader saved
        phases["save_s"] = stopped.result()
        try:
            saved = snapshot.read(saved_path)
            if args.control == "drop_saved":
                saved = control.drop_saved(*saved)
        except ValueError as e:
            saved = str(e)
        wc.check_saved(probes, saved, hash_keys,
                       [warmup_request(w).hash_key() for w in range(n_workers)],
                       verdict)
        say(f"shutdown: save_s={phases['save_s']:.3f} (SIGTERM to exit, the "
            f"drain and the Save)")
    for line in verdict.lines():
        say(line)
    say("counted: " + " ".join(f"{k}={n}" for k, n in wc.counted.items()))
    t_checked = time.monotonic()
    stopped.result()  # a BenchFailure of the stop is the run's
    stopping.shutdown()
    say(f"after the window: probes_s={t_probed - t_closed:.3f} "
        f"compare_s={t_checked - t_probed:.3f} "
        f"stop_s={time.monotonic() - t_probed:.3f} (compare and stop overlap)")

    # counts: items
    in_window = res["done"] <= seconds
    attempted = int(res["sizes"].sum())
    failed = int(res["sizes"][failed_call].sum()) + int(np.sum(~items.valid))
    if res["first_error"]:
        say(f"first item error: {res['first_error']}")
    good_items_in_window = int(res["sizes"][res["ok"] & in_window].sum()) - int(
        np.sum(~items.valid))
    n_calls = len(res["call"])
    fifths = np.histogram(res["done"][res["ok"]], bins=5, range=(0.0, seconds))[0]
    say(f"window fifths (calls completed): {fifths.tolist()}")
    # a freeze of the host or the server shows as a gap with no reply at all
    ends = np.sort(np.concatenate([
        [0.0], res["done"][res["ok"] & (res["done"] <= seconds)], [seconds]]))
    gap_at = int(np.argmax(np.diff(ends)))
    say(f"longest gap between replies: {np.diff(ends)[gap_at]:.3f} s, from "
        f"{ends[gap_at]:.3f} s; call deadline {deadline_s} s; "
        f"failed calls by gRPC status: {res['rpc_errors']}")
    say(f"window: calls={n_calls} completed_in_window={int(np.sum(in_window & res['ok']))} "
        f"failed_calls={int(np.sum(failed_call))} items_attempted={attempted} "
        f"items_failed={failed}")

    values = {"setup_s": setup_s}
    if plan.loop == "closed":
        values["decisions_per_s"] = good_items_in_window / seconds
    else:
        every = open_loop_readings(res, np.ones(n_calls, dtype=bool), deadline_s)
        values.update({k: v for k, v in every.items() if k.startswith("call_")})
        say(f"open loop: calls={n_calls} beyond_p99={stats.beyond(n_calls, 99)} "
            + " ".join(f"{k}={v:.4f}" for k, v in every.items()))
        if args.platform == "tpu":
            require(int(np.sum(res["ok"])) >= P99_MIN_CALLS,
                    f"only {int(np.sum(res['ok']))} calls completed: a p99 "
                    f"needs {P99_MIN_CALLS}")

    trace = None
    if args.trace:
        require("trace_dir" in trace_out and "scrape" in trace_out,
                f"/debug/profile gave no trace: {trace_out.get('error')}")
        trace = reduce_trace(trace_out["trace_dir"], work)
        shutil.rmtree(os.path.join(work, "tmp"), ignore_errors=True)  # tens of MB
        say(f"trace: planes={trace['plane_names']} device_planes="
            f"{len(trace['devices'])} busy_s={trace['busy_s']} "
            f"window_s={trace['window_s']} stop_trace_s={trace_out.get('stop_s')}")

    device = device_object(dev_after)
    wanted = manifest.metrics_of(m, cell["name"],
                                 "per_layer" if args.trace else "end_to_end")
    metrics = {}
    if args.trace:
        # host-clock and counter readings lie between the two scrapes: they
        # end where the profiler, whose start and stop stall the server, starts
        t_lo, t_hi = trace_out["scrape0_at_s"], trace_out["scrape_at_s"]
        pre = res["ok"] & (res["done"] > t_lo) & (res["done"] <= t_hi)
        gen = {}
        if plan.loop == "open":
            due_in = (res["due"] > t_lo) & (res["due"] <= t_hi)
            n_in = int(np.sum(due_in))
            require(args.platform != "tpu" or n_in >= P99_MIN_CALLS,
                    f"only {n_in} calls were due before the profile: a p99 "
                    f"needs {P99_MIN_CALLS}")
            if n_in:
                gen = open_loop_readings(res, due_in, deadline_s)
                say(f"open loop, due in ({t_lo:.2f}, {t_hi:.2f}] s: calls={n_in} "
                    f"beyond_p99={stats.beyond(n_in, 99)} "
                    + " ".join(f"{k}={v:.4f}" for k, v in gen.items()))
        ctx = readers.Context(
            before=trace_out["scrape0"], after=trace_out["scrape"], device=dev_after,
            phases=dict(phases, setup_s=setup_s),
            generator=gen, trace=trace, conf=conf, traffic=traf,
            table=table, items_answered=int(res["sizes"][pre].sum()), root=ROOT,
        )
        for x in wanted:
            v = readers.read(manifest.reader_path(ROOT, manifest.bench_dir(m), x["name"]), ctx)
            if v is not None:
                metrics[x["name"]] = {"value": v, "unit": x["unit"]}
            say(f"per_layer {x['name']}: {v} {x['unit']}")
        if trace["devices"]:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
    else:
        for x in wanted:
            require(x["name"] in values, f"the cell does not measure {x['name']}")
            metrics[x["name"]] = {"value": values[x["name"]], "unit": x["unit"]}
    if args.platform == "cpu":
        # a CPU run yields counts, never a time, a rate or a share
        metrics = {n: {"value": None, "unit": v["unit"]} for n, v in metrics.items()}
        device.pop("busy_s", None)
        device.pop("window_s", None)
    result = {
        "correct": verdict.correct, "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device,
    }
    if args.trace and trace and trace["devices"] and args.platform != "cpu":
        result["breakdown"] = trace["breakdown"]
    # the parent's own clock over set-up and, where a Save is checked, shutdown
    result["phases"] = {k: None if args.platform == "cpu" else v
                        for k, v in phases.items()}
    # what the window held for the exact rows to work on (no limit: counts)
    result["counted"] = wc.counted
    # every number compared beside its limit, where a record of a run that
    # came out not correct keeps them: the end of stderr and of the result line
    result["checks"] = verdict.as_dict()
    print("\n".join(verdict.lines()), file=sys.stderr, flush=True)
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
