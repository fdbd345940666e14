#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the served path starts, places
its table and answers on a TPU.

    python chip_smoke.py                 one chip, 1M keys in 2M slots
    python chip_smoke.py --chips 4       the four-chip host: ICI daemon,
                                         then four one-chip daemons
    python chip_smoke.py --platform cpu --keys 2000     rehearsal

This process never imports JAX: a parent that touched JAX would hold
the chip its child needs. It starts ``python -m gubernator_tpu.cmd.daemon``
as the one process on the chip, loads ``--keys`` distinct keys made from
``--seed`` over gRPC in calls of 1,000 items, walks the kernel's
branches with a few more calls, and compares EVERY response with
``models/oracle.py`` fed the same requests in the same order. Then it
reads back from the daemon what a log line cannot fake: the platform
JAX initialised, real allocator bytes, columnar flushes, cold compiles;
restarts the daemon and requires persistent-compile-cache hits.

Any failed check or child crash is a non-zero exit with no result line.
On success the last line of stdout is one JSON object naming the device
as JAX reported it. What it prints besides are set-up facts from one
run, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import random
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np

from gubernator_tpu import native
from gubernator_tpu.api.types import (
    MAX_BATCH_SIZE,
    Algorithm,
    Behavior,
    RateLimitReq,
    RateLimitResp,
    Status,
)
from gubernator_tpu.client import SyncGubernatorClient
from gubernator_tpu.models.oracle import OracleEngine
from gubernator_tpu.utils import gregorian

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, "chiprun_out")
NAME = "smoke"
DURATION_MS = 3_600_000  # nothing loaded expires inside a run
WAYS = 8  # table associativity (EngineConfig.ways)
SLOT_BYTES = 80  # nominal fused-layout bytes per slot: 10 int64 columns
DEADLINE_S = 1150  # whole-run watchdog, inside the driver's 1200 s
START_TIMEOUT_S = 900  # one server, exec to healthy
BAD_LOG_LINES = (
    "Traceback",
    "native library",
    "bucket warm-up failed",
    "sync tick failed",
)


class SmokeFailure(Exception):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


# ---- children ---------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """One server process with its output in a log file."""

    def __init__(self, label: str, module: str, argv: list, env: dict):
        os.makedirs(LOG_DIR, exist_ok=True)
        self.label = label
        self.log_path = os.path.join(LOG_DIR, f"chip_smoke_{label}.log")
        self.t_exec = time.monotonic()
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", module, *argv],
                cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
            )

    def log_text(self) -> str:
        with open(self.log_path, encoding="utf-8", errors="replace") as f:
            return f.read()

    def require_running(self) -> None:
        rc = self.proc.poll()
        require(
            rc is None,
            f"{self.label} exited rc={rc}; log tail:\n{self.log_text()[-3000:]}",
        )

    def require_clean_log(self) -> None:
        text = self.log_text()
        for bad in BAD_LOG_LINES:
            require(
                bad not in text,
                f"{self.label} log holds {bad!r}:\n{text[-3000:]}",
            )

    def terminate(self, timeout_s: float = 120.0) -> int:
        self.proc.send_signal(signal.SIGTERM)
        return self.proc.wait(timeout=timeout_s)

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def child_env(platform: str, n_devices: int, extra: dict) -> dict:
    """The caller's environment minus any GUBER_* setting, plus ours.
    JAX_PLATFORMS is left alone unless --platform cpu asked for the
    rehearsal: on the chip host nobody sets it, and if JAX then quietly
    initialises the CPU the platform check below fails the run."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("GUBER_")}
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n_devices}"
        )
    env.update(extra)
    return env


def http_json(addr: str, path: str, body=None, timeout: float = 60.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        f"http://{addr}{path}", data=data,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def metrics_text(addr: str) -> str:
    with urllib.request.urlopen(f"http://{addr}/metrics", timeout=60) as r:
        return r.read().decode()


def metric(text: str, series: str) -> float:
    """Value of one exposition line, e.g.
    'gubernator_engine_flush_duration_count{path="columnar"}'."""
    for line in text.splitlines():
        if line.startswith(series + " "):
            return float(line.rsplit(" ", 1)[1])
    raise SmokeFailure(f"/metrics has no series {series}")


def wait_until(what: str, cond, timeout_s: float) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond():
        require(time.monotonic() < deadline, f"timed out waiting for {what}")
        time.sleep(0.25)


def wait_healthy(child: Child, http_addr: str) -> float:
    """Seconds from exec until /v1/HealthCheck answers healthy."""

    def healthy() -> bool:
        child.require_running()
        try:
            return http_json(http_addr, "/v1/HealthCheck", timeout=5)[
                "status"
            ] == "healthy"
        except (urllib.error.URLError, OSError):
            return False

    wait_until(f"{child.label} to be healthy", healthy, START_TIMEOUT_S)
    return time.monotonic() - child.t_exec


# ---- the oracle side --------------------------------------------------------


class Checker:
    """Sends calls and holds the daemon to the oracle: the same requests
    in the same order must give the same status, remaining, reset_time
    and limit, with no error."""

    def __init__(self, client: SyncGubernatorClient):
        self.client = client
        self.oracle = OracleEngine()
        self.calls = 0  # gRPC calls whose items belong on the columnar lane
        self.items = 0
        self.mismatches = 0
        self.examples: list = []

    def compare(self, what, reqs, got, now_ms) -> None:
        require(
            len(got) == len(reqs),
            f"{what}: {len(got)} responses for {len(reqs)} requests",
        )
        want = self.oracle.get_rate_limits(reqs, now_ms)
        self.items += len(reqs)
        for r, g, w in zip(reqs, got, want):
            g_t = (int(g.status), g.remaining, g.reset_time, g.limit, g.error)
            w_t = (int(w.status), w.remaining, w.reset_time, w.limit, w.error)
            if g_t != w_t:
                self.mismatches += 1
                if len(self.examples) < 5:
                    self.examples.append(
                        f"{what} key={r.unique_key} hits={r.hits} "
                        f"limit={r.limit} algo={int(r.algorithm)} "
                        f"behavior={int(r.behavior)}: got {g_t} want {w_t}"
                    )

    def call(self, what: str, reqs: list, now_ms: int,
             columnar: bool = True) -> list:
        got = self.client.get_rate_limits(reqs)
        self.calls += columnar
        self.compare(what, reqs, got, now_ms)
        return got

    def require_exact(self, phase: str) -> None:
        require(
            self.mismatches == 0,
            f"{phase}: {self.mismatches} mismatches against OracleEngine "
            f"in {self.items} items; first: " + " | ".join(self.examples),
        )


def load_requests(seed: int, n: int, t0: int, behavior: int = 0,
                  prefix: str = "k", limit_lo: int = 1) -> list:
    """`n` distinct keys from `seed`; every fourth is a leaky bucket
    (BASELINE.json config 3's mix). Small limits, so some first hits are
    already over the limit. created_at is pinned: answers do not depend
    on the daemon's wall clock."""
    rng = random.Random(seed)
    return [
        RateLimitReq(
            name=NAME,
            unique_key=f"{prefix}{i:07d}-{rng.getrandbits(40):010x}",
            hits=rng.randrange(0, 4),
            limit=limit_lo + rng.randrange(100),
            duration=DURATION_MS,
            algorithm=(
                Algorithm.LEAKY_BUCKET if i % 4 == 0
                else Algorithm.TOKEN_BUCKET
            ),
            behavior=behavior,
            created_at=t0,
        )
        for i in range(n)
    ]


def never_evicted(reqs: list, num_groups: int, ways: int) -> tuple:
    """(indices of the requests whose slot group received at most `ways`
    of these keys, keys per group). The table is set-associative with
    LRU eviction inside a group, and the oracle has no capacity: only
    keys whose group never overflowed are guaranteed resident. Groups
    come from the same native hash the daemon uses."""
    _, _, grp = native.hash128_batch([r.hash_key() for r in reqs], num_groups)
    counts = np.bincount(grp, minlength=num_groups)
    return np.nonzero(counts[grp] <= ways)[0].tolist(), counts


def spread(indices: list, k: int) -> list:
    k = min(k, len(indices))
    return [indices[i] for i in np.linspace(0, len(indices) - 1, k, dtype=int)]


def probe(r: RateLimitReq, created_at=None) -> RateLimitReq:
    return RateLimitReq(
        name=r.name, unique_key=r.unique_key, hits=0, limit=r.limit,
        duration=r.duration, algorithm=r.algorithm, behavior=r.behavior,
        created_at=created_at,
    )


def run_load(chk: Checker, reqs: list, t0: int) -> None:
    for off in range(0, len(reqs), MAX_BATCH_SIZE):
        chk.call("load", reqs[off:off + MAX_BATCH_SIZE], t0)
    chk.require_exact("load")


def run_census_check(http_addr: str, counts, ways: int, tier: str = ""):
    """The table's own census must count exactly the keys its geometry
    can hold: sum over groups of min(keys in group, ways)."""
    expected = int(np.minimum(counts, ways).sum())

    def live() -> int:
        snap = http_json(http_addr, "/debug/table")
        return snap["tiers"][tier]["live"] if tier else snap["live"]

    # census snapshots are TTL-cached: the first read may be stale
    wait_until(f"table census live == {expected} resident keys",
               lambda: live() == expected, 30)
    return expected


def run_branches(chk: Checker, loaded: list, resident: list, t0: int,
                 http_addr: str) -> None:
    """A few calls that walk the kernel's branches, each against the
    oracle. The pinned clock advances 500 ms per call so leaky buckets
    leak between calls."""
    clock = [t0]

    def req(key, hits, limit=10, algo=Algorithm.TOKEN_BUCKET, behavior=0,
            duration=DURATION_MS):
        return RateLimitReq(
            name=NAME, unique_key=key, hits=hits, limit=limit,
            duration=duration, algorithm=algo, behavior=behavior,
        )

    def stamp(reqs) -> list:
        clock[0] += 500
        for r in reqs:
            r.created_at = clock[0]
        return list(reqs)

    def call(what, *reqs, now_ms=None, columnar=True) -> list:
        return chk.call(what, stamp(reqs), now_ms or clock[0], columnar)

    for tag, algo in (("tok", Algorithm.TOKEN_BUCKET),
                      ("leak", Algorithm.LEAKY_BUCKET)):
        # over the limit: rejected, and nothing consumed
        k = f"branch-over-{tag}"
        call("over/take7", req(k, 7, algo=algo))
        got = call("over/take5", req(k, 5, algo=algo))
        require(got[0].status == Status.OVER_LIMIT, f"{k}: not OVER_LIMIT")
        call("over/probe", req(k, 0, algo=algo))
        call("reset", req(k, 1, algo=algo, behavior=Behavior.RESET_REMAINING))
        call("reset/after", req(k, 1, algo=algo))
        k = f"branch-drain-{tag}"
        call("drain/take4", req(k, 4, algo=algo))
        got = call(
            "drain/take20",
            req(k, 20, algo=algo, behavior=Behavior.DRAIN_OVER_LIMIT),
        )
        require(
            got[0].status == Status.OVER_LIMIT and got[0].remaining == 0,
            f"{k}: DRAIN_OVER_LIMIT did not drain",
        )
        call("drain/probe", req(k, 0, algo=algo))

    # one key 900 times in one call: more waves than a columnar flush
    # takes (max_waves), so the pump serves it with carry-over; the flip
    # to OVER_LIMIT comes in the middle of the call
    call("same-key-900", *(req("branch-hot", 1, limit=600) for _ in range(900)),
         columnar=False)

    # hits=0 probes of keys spread over the loaded set
    call("probe-loaded",
         *(probe(loaded[i]) for i in spread(resident, MAX_BATCH_SIZE)))

    # one DURATION_IS_GREGORIAN item in a mixed call: it leaves the
    # columnar lane for the object path; its reset is the end of the
    # daemon's current UTC day, the same day as ours
    mixed = [req(f"branch-mixed-{i}", 1) for i in range(9)]
    mixed.insert(4, req(
        "branch-gregorian", 1, duration=gregorian.GREGORIAN_DAYS,
        behavior=Behavior.DURATION_IS_GREGORIAN,
    ))
    call("mixed-gregorian", *mixed, now_ms=int(time.time() * 1000))

    # one call over HTTP/JSON
    http_reqs = stamp([
        req("branch-over-tok", 1), req("branch-http", 3, limit=5),
        req("branch-http", 3, limit=5),
        req("branch-http-leaky", 2, algo=Algorithm.LEAKY_BUCKET),
    ])
    body = {"requests": [
        {"name": r.name, "unique_key": r.unique_key, "hits": r.hits,
         "limit": r.limit, "duration": r.duration,
         "algorithm": int(r.algorithm), "behavior": int(r.behavior),
         "created_at": r.created_at}
        for r in http_reqs
    ]}
    got = [
        RateLimitResp(
            status=Status[j["status"]], limit=int(j["limit"]),
            remaining=int(j["remaining"]), reset_time=int(j["reset_time"]),
            error=j.get("error", ""),
        )
        for j in http_json(http_addr, "/v1/GetRateLimits", body)["responses"]
    ]
    chk.compare("http-json", http_reqs, got, clock[0])
    chk.require_exact("branches")


# ---- what the daemon reports about itself -----------------------------------


def read_device(http_addr: str, platform: str, min_devices: int) -> dict:
    """/debug/device, refused unless it names the expected platform."""
    dev = http_json(http_addr, "/debug/device")
    require(
        dev["platform"] == platform,
        f"daemon runs on platform={dev['platform']!r}, expected "
        f"{platform!r}: JAX found no accelerator",
    )
    require(
        dev["device_count"] >= min_devices,
        f"daemon sees {dev['device_count']} device(s), need {min_devices}",
    )
    if platform == "tpu":
        require(
            dev["memory"]["source"] == "device",
            "memory.source is not the device allocator: "
            + dev["memory"]["source"],
        )
    return dev


def require_served_columnar(http_addr: str, grpc_calls: int) -> None:
    text = metrics_text(http_addr)
    flushes = metric(
        text, 'gubernator_engine_flush_duration_count{path="columnar"}'
    )
    require(
        flushes >= grpc_calls,
        f"{flushes:.0f} columnar flushes for {grpc_calls} gRPC calls: the "
        "native wire path did not serve",
    )
    cold = metric(text, "gubernator_engine_cold_compile_count")
    require(cold == 0, f"cold_compile_count={cold:.0f} on the serving path")
    say(f"columnar_flushes={flushes:.0f} grpc_calls={grpc_calls} "
        f"cold_compile_count={cold:.0f}")


def print_start_facts(label: str, dev: dict, start_s: float,
                      nominal: int) -> None:
    """`nominal`: what the tables would take at SLOT_BYTES per slot."""
    mem, comp = dev["memory"], dev["compile"]
    say(f"{label}: start_to_healthy_s={start_s:.1f} "
        f"compiles={comp['compiles']} "
        f"compile_seconds={comp['compile_seconds']:.1f} "
        f"cache_hits={comp['cache_hits']} cache_path={comp['path']}")
    ratio = (
        f"{mem['bytes_in_use'] / nominal:.2f}" if mem["source"] == "device"
        else "not measured"
    )
    say(f"{label}: memory_source={mem['source']} "
        f"bytes_in_use={mem['bytes_in_use']} nominal_table_bytes={nominal} "
        f"ratio={ratio}")
    for row in mem["devices"]:
        say(f"{label}: device id={row['id']} kind={row['device_kind']} "
            f"bytes_in_use={row['bytes_in_use']}")


# ---- phases -----------------------------------------------------------------


def start_daemon(args, label: str, extra: dict, children: list):
    """(child, grpc address, http address, seconds exec -> healthy)."""
    grpc_addr, http_addr = (f"127.0.0.1:{free_port()}" for _ in range(2))
    env = {
        "GUBER_GRPC_ADDRESS": grpc_addr,
        "GUBER_HTTP_ADDRESS": http_addr,
        "GUBER_CACHE_SIZE": str(args.cache_size),
        "GUBER_PREWARM_BUCKETS": "true",
        **extra,
    }
    child = Child(label, "gubernator_tpu.cmd.daemon", [],
                  child_env(args.platform, args.chips, env))
    children.append(child)
    return child, grpc_addr, http_addr, wait_healthy(child, http_addr)


def stop_daemon(child: Child) -> None:
    rc = child.terminate()
    require(rc == 0, f"{child.label} exited rc={rc} after SIGTERM")
    require(
        "drain complete" in child.log_text(),
        f"{child.label} log never reached 'drain complete'",
    )
    child.require_clean_log()


def serve_and_check(args, child: Child, grpc_addr: str, http_addr: str,
                    t0: int, tier: str = "") -> Checker:
    """Load, census, branches, and the daemon's own counters."""
    table = http_json(http_addr, "/debug/table")
    if tier:
        table = table["tiers"][tier]
    loaded = load_requests(args.seed, args.keys, t0)
    resident, counts = never_evicted(loaded, table["groups"], table["ways"])
    with SyncGubernatorClient(grpc_addr, default_timeout=120.0) as client:
        chk = Checker(client)
        t = time.monotonic()
        run_load(chk, loaded, t0)
        say(f"keys_loaded={len(loaded)} calls={chk.calls} "
            f"mismatches={chk.mismatches} load_wall_s="
            f"{time.monotonic() - t:.1f} slots={table['slots']}")
        live = run_census_check(http_addr, counts, table["ways"], tier)
        say(f"census_live={live} never_evicted_keys={len(resident)}")
        run_branches(chk, loaded, resident, t0, http_addr)
        say(f"branch_items={chk.items - len(loaded)} "
            f"mismatches={chk.mismatches}")
    child.require_running()
    require_served_columnar(http_addr, chk.calls)
    return chk


def phase_one_chip(args, children: list) -> dict:
    t0 = int(time.time() * 1000) - 120_000
    cache_size = args.cache_size
    child, grpc_addr, http_addr, cold_s = start_daemon(
        args, "daemon_cold", {}, children
    )
    dev = read_device(http_addr, args.platform, 1)
    say(f"platform={dev['platform']} device_kind={dev['device_kind']} "
        f"device_count={dev['device_count']}")
    print_start_facts("cold", dev, cold_s, cache_size * SLOT_BYTES)
    serve_and_check(args, child, grpc_addr, http_addr, t0)
    stop_daemon(child)

    # Restart: the only check that the compile cache works on the chip.
    child, grpc_addr, http_addr, warm_s = start_daemon(
        args, "daemon_warm", {}, children
    )
    dev2 = read_device(http_addr, args.platform, 1)
    print_start_facts("warm", dev2, warm_s, cache_size * SLOT_BYTES)
    if args.platform == "tpu":
        # A CPU-pinned rehearsal runs uncached unless the caller set
        # JAX_COMPILATION_CACHE_DIR (utils/compilecache.py).
        require(
            dev2["compile"]["cache_hits"] > 0,
            "no compile-cache hit on the second start: "
            + json.dumps(dev2["compile"]),
        )
    with SyncGubernatorClient(grpc_addr) as client:
        chk = Checker(client)
        chk.call("after-restart", load_requests(args.seed, 10, t0), t0)
        chk.require_exact("after restart")
    stop_daemon(child)
    return dev


def phase_ici(args, children: list) -> dict:
    """One daemon over the whole host: the table sharded over the chips,
    GLOBAL keys on per-chip replicas synced by collectives."""
    t0 = int(time.time() * 1000) - 120_000
    n_global = max(args.keys // 10, 10)
    ici = {
        "GUBER_GLOBAL_MODE": "ici",
        "GUBER_ICI_NUM_GROUPS": str(args.cache_size // WAYS),
        "GUBER_ICI_NUM_SLOTS": str(args.cache_size // 2),
    }
    child, grpc_addr, http_addr, cold_s = start_daemon(
        args, "daemon_ici", ici, children
    )
    dev = read_device(http_addr, args.platform, args.chips)
    say(f"ici: platform={dev['platform']} device_kind={dev['device_kind']} "
        f"device_count={dev['device_count']}")
    # the sharded table once, plus a replica table (and its 8-byte
    # pending delta per slot) on every chip
    print_start_facts(
        "ici", dev, cold_s,
        args.cache_size * SLOT_BYTES
        + args.chips * (args.cache_size // 2) * (SLOT_BYTES + 8),
    )
    rows = dev["memory"]["devices"]
    require(len(rows) == args.chips, f"ici engine spans {len(rows)} devices")
    chk = serve_and_check(args, child, grpc_addr, http_addr, t0, "sharded")

    # GLOBAL: every key hit in two passes, so most keys are counted on
    # two different replicas and only the collective sync can add them.
    rnum = http_json(http_addr, "/debug/table")["tiers"]["replica"]
    first = load_requests(args.seed + 1, n_global, t0, Behavior.GLOBAL,
                          prefix="g", limit_lo=1000)
    resident, _ = never_evicted(first, rnum["groups"], rnum["ways"])
    second = [
        RateLimitReq(
            name=r.name, unique_key=r.unique_key, hits=1 + i % 3,
            limit=r.limit, duration=r.duration, algorithm=r.algorithm,
            behavior=r.behavior, created_at=t0,
        )
        for i, r in enumerate(first)
    ]
    with SyncGubernatorClient(grpc_addr, default_timeout=120.0) as client:
        gchk = Checker(client)
        for off in range(0, n_global, MAX_BATCH_SIZE):
            gchk.call("global/first", first[off:off + MAX_BATCH_SIZE], t0)
        gchk.require_exact("global first pass")
        for off in range(0, n_global, MAX_BATCH_SIZE):
            # Stale by design until the next tick: fed to the oracle for
            # the totals, not compared.
            batch = second[off:off + MAX_BATCH_SIZE]
            client.get_rate_limits(batch)
            gchk.oracle.get_rate_limits(batch, t0)
        # Quiescence: the backlog drains, then three more ticks pass.
        def gauge(name: str) -> float:
            return metric(metrics_text(http_addr), name)

        wait_until("the GLOBAL sync backlog to drain",
                   lambda: gauge("gubernator_global_sync_backlog") == 0, 300)
        ticks = gauge("gubernator_ici_tick_duration_count")
        wait_until(
            "three more ICI ticks",
            lambda: gauge("gubernator_ici_tick_duration_count") >= ticks + 3,
            60,
        )
        picks = spread(resident, MAX_BATCH_SIZE)
        gchk.call("global/probe", [probe(first[i], t0) for i in picks], t0)
        gchk.require_exact("GLOBAL probes after quiescence")
        say(f"ici: global_keys={n_global} probes={len(picks)} "
            f"mismatches={gchk.mismatches} ticks={ticks:.0f}")
    dev = read_device(http_addr, args.platform, args.chips)
    used = [r["bytes_in_use"] for r in dev["memory"]["devices"]]
    say(f"ici: per_device_bytes_in_use={used}")
    if args.platform == "tpu":
        require(
            min(used) > 0 and max(used) < 2 * min(used),
            f"table not spread over the devices: bytes_in_use={used}",
        )
    require_served_columnar(http_addr, chk.calls)
    stop_daemon(child)
    return dev


def phase_cluster(args, children: list) -> None:
    """Four one-chip daemons in one process behind the hash ring."""
    t0 = int(time.time() * 1000) - 120_000
    n = args.chips
    cache = args.cache_size // 4
    child = Child(
        "cluster", "gubernator_tpu.cmd.cluster",
        ["-n", str(n), "--cache-size", str(cache)],
        child_env(args.platform, n, {}),
    )
    children.append(child)
    def ready_line() -> bool:
        child.require_running()
        return "READY " in child.log_text()

    wait_until("the cluster's READY line", ready_line, START_TIMEOUT_S)
    ready = next(line for line in child.log_text().splitlines()
                 if line.startswith("READY "))
    addrs = json.loads(ready[len("READY "):])
    say(f"cluster: start_to_ready_s={time.monotonic() - child.t_exec:.1f} "
        f"daemons={len(addrs)}")
    ids = []
    for a in addrs:
        dev = read_device(a["http"], args.platform, n)
        rows = dev["memory"]["devices"]
        require(len(rows) == 1, f"daemon {a['http']} spans {len(rows)} devices")
        ids.append(rows[0]["id"])
        say(f"cluster: daemon {a['grpc']} device id={rows[0]['id']} "
            f"bytes_in_use={rows[0]['bytes_in_use']}")
        if args.platform == "tpu":
            require(
                rows[0]["bytes_in_use"] >= cache * SLOT_BYTES,
                f"device {rows[0]['id']} holds less than one table",
            )
    require(len(set(ids)) == n, f"daemons share devices: {ids}")

    # Every key goes through every daemon in turn: one shared count per
    # key means each non-owner forwarded to the owner.
    reqs = load_requests(args.seed + 2, 400, t0, prefix="c", limit_lo=50)
    chk = Checker(None)
    owners = set()
    for a in addrs:
        with SyncGubernatorClient(a["grpc"], default_timeout=60.0) as client:
            chk.client = client
            got = chk.call("cluster", [
                RateLimitReq(
                    name=r.name, unique_key=r.unique_key, hits=1,
                    limit=r.limit, duration=r.duration,
                    algorithm=r.algorithm, created_at=t0,
                ) for r in reqs
            ], t0)
        owners.update(g.metadata.get("owner", "") for g in got)
    chk.require_exact("cluster")
    say(f"cluster: items={chk.items} mismatches={chk.mismatches} "
        f"owners={sorted(owners - {''})}")
    require(
        owners - {""} == {a["grpc"] for a in addrs},
        f"owners named in responses {sorted(owners)} are not the "
        f"{n} daemons",
    )
    for a in addrs:
        served = metric(metrics_text(a["http"]),
                        "gubernator_engine_flush_duration_count"
                        '{path="columnar"}')
        require(served > 0, f"daemon {a['grpc']} served nothing on its chip")
    child.require_running()
    rc = child.terminate()
    require(rc == 0, f"cluster exited rc={rc} after SIGTERM")
    child.require_clean_log()


# ---- main -------------------------------------------------------------------


def installed_version(pkg: str) -> str:
    """From package metadata: importing jax here would take the chip."""
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "absent"


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--keys", type=int, default=1_000_000,
                   help="distinct keys to load; the table gets the next "
                   "power of two >= 2x as many slots")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: the four-chip host (ICI daemon, then four "
                   "one-chip daemons in one process)")
    p.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                   help="cpu is the explicit rehearsal; nothing selects "
                   "it implicitly")
    args = p.parse_args()
    args.cache_size = 1
    while args.cache_size < 2 * args.keys:
        args.cache_size <<= 1

    def on_alarm(signum, frame):
        raise SmokeFailure(f"run exceeded {DEADLINE_S}s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(DEADLINE_S)

    say("versions: " + " ".join(
        f"{pkg}={installed_version(pkg)}" for pkg in ("jax", "jaxlib", "libtpu")
    ))
    # Build the native hasher here first: the probe selection needs the
    # daemon's own hash, and the daemon then finds the library built.
    require(native.available(),
            "native hasher unavailable: " + native.unavailable_reason)
    children: list = []
    try:
        if args.chips == 1:
            dev = phase_one_chip(args, children)
        else:
            dev = phase_ici(args, children)
            phase_cluster(args, children)
    finally:
        for c in children:
            c.kill()
    say(json.dumps({
        "ok": True,
        "device": {
            "platform": dev["platform"],
            "kind": dev["device_kind"],
            "count": dev["device_count"],
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
