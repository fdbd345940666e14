"""The one general traffic generator: a traffic file's parameters plus a
configuration's keyspace plus ``--seed`` give a plan of calls.

A traffic mix is data (``benchmarks/traffic/<name>.json``):

    loop            "closed" | "open"
    callers         closed loop: concurrent callers, each sending its next
                    call when the reply arrives
    rate_calls_per_s  open loop: offered rate, fixed in the file
    arrivals        open loop: {"kind": "poisson"} or
                    {"kind": "bursts", "calls": 50, "every_ms": 100}
    items_per_call  a number, or {"2": 0.7, "100": 0.25, "1000": 0.05}
    hits            hits an item asks for: a number (1), or a share table over
                    the items, {"1": 0.8, "2": 0.1, "5": 0.08, "20": 0.02}
    keys            {"distribution": "uniform"}
                    {"distribution": "zipf", "s": 0.99, "scrambled": true}
                    {"distribution": "hotset", "hot_keys": 100, "hot_share": 0.9}
    behavior_shares optional [{"share": 0.9, "behavior": []},
                    {"share": 0.1, "behavior": ["RESET_REMAINING"]}];
                    default: the keyspace's own behaviour on every item
    pool_calls      closed loop: calls made per caller before the window
                    (a caller that exhausts its pool starts it again)
    workers         load-generator processes (a worker spreads its callers,
                    or its calls, over the addresses run.py hands it; run.py
                    starts one server today)
    setup_check_calls  sequential calls compared before the window (200)
    trace_seconds   length of a traced run's profile (3)
    rehearsal       overrides for the --platform cpu rehearsal at a tiny size

Every seed gets the same multiset of sizes and of inter-arrival gaps in
another order (the gaps are the exponential's quantiles, permuted), so
the seed changes which keys meet, not how much work a run holds.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import cached_property

import numpy as np

from benchmarks import wire
from benchmarks.reference.oracle import LEAKY_BUCKET, TOKEN_BUCKET, Request

_MASK64 = (1 << 64) - 1


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent streams of one --seed (any whole number: the driver's
    are above 2**31)."""
    return np.random.default_rng([int(seed) & _MASK64, stream])


# ---- keyspace (the configuration's side) ------------------------------------


@dataclass
class Keyspace:
    """The configuration's keys: ``n`` of them, named from the seed, with
    the algorithm, limit and duration the configuration's file states.
    ``behavior`` is every key's; ``behavior_of_keys`` in the file, a list of
    {"one_in": 4, "behavior": ["DRAIN_OVER_LIMIT"]}, adds flags that are part
    of some limits' definition: every request of such a key carries them."""

    name: str
    n: int
    limit: int
    duration_ms: int
    algorithm: str  # "token" | "leaky" | "even_token_odd_leaky"
    behavior: int
    salt: int
    key_flags: tuple = ()  # ((one_in, bits), ...) from `behavior_of_keys`

    @classmethod
    def from_config(cls, conf: dict, seed: int) -> "Keyspace":
        ks = conf["keyspace"]
        return cls(
            name=ks.get("name", "bench"),
            n=int(ks["keys"]),
            limit=int(ks["limit"]),
            duration_ms=int(ks["duration_ms"]),
            algorithm=ks["algorithm"],
            behavior=behavior_bits(ks.get("behavior", [])),
            salt=int(rng_for(seed, 0).integers(0, 1 << 40)),
            key_flags=tuple((int(r["one_in"]), behavior_bits(r["behavior"]))
                            for r in ks.get("behavior_of_keys", ())),
        )

    @cached_property
    def flags(self) -> np.ndarray:
        """Each key's own behaviour: a rule's flags sit on the keys whose id
        mixes (``scramble``) to 0 modulo its `one_in`, whatever their rank
        and algorithm."""
        out = np.full(self.n, self.behavior, dtype=np.int64)
        ids = np.arange(self.n, dtype=np.int64)
        for one_in, bits in self.key_flags:
            out[scramble(ids, one_in) == 0] |= bits
        return out

    def unique_key(self, key_id: int) -> str:
        return f"k{key_id:08d}-{self.salt:010x}"

    def algorithm_of(self, key_id: int) -> int:
        if self.algorithm == "token":
            return TOKEN_BUCKET
        if self.algorithm == "leaky":
            return LEAKY_BUCKET
        if self.algorithm == "even_token_odd_leaky":
            return LEAKY_BUCKET if key_id % 2 else TOKEN_BUCKET
        raise ValueError(f"unknown keyspace algorithm {self.algorithm!r}")

    def is_token(self, key_ids: np.ndarray) -> np.ndarray:
        if self.algorithm == "token":
            return np.ones(len(key_ids), dtype=bool)
        if self.algorithm == "leaky":
            return np.zeros(len(key_ids), dtype=bool)
        return key_ids % 2 == 0

    def request(self, key_id: int, hits: int, created_at=None,
                behavior=None) -> Request:
        if behavior is None:
            behavior = int(self.flags[key_id]) if self.key_flags else self.behavior
        return Request(
            name=self.name, unique_key=self.unique_key(int(key_id)),
            hits=hits, limit=self.limit, duration=self.duration_ms,
            algorithm=self.algorithm_of(int(key_id)),
            behavior=behavior,
            created_at=created_at,
        )


def behavior_bits(flags) -> int:
    bits = 0
    for f in flags:
        bits |= wire.BEHAVIOR[f]
    return bits


# ---- key draws ---------------------------------------------------------------


def zipf_cdf(n: int, s: float) -> np.ndarray:
    """Cumulative mass of ranks 1..n under p(r) = r**-s / H(n, s)."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -s
    c = np.cumsum(w)
    return c / c[-1]


def scramble(ranks: np.ndarray, n: int) -> np.ndarray:
    """YCSB's scrambled zipfian: the popular ranks are spread over the
    keyspace by a fixed 64-bit mix (splitmix64's finaliser) instead of
    sitting at the low ids. Two ranks may land on one id, as in YCSB."""
    z = ranks.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    z = z ^ (z >> np.uint64(31))
    return (z % np.uint64(n)).astype(np.int64)


def draw_keys(spec: dict, n_keys: int, count: int,
              rng: np.random.Generator) -> np.ndarray:
    """`count` key ids in [0, n_keys) under the traffic file's `keys`."""
    dist = spec.get("distribution", "uniform")
    if dist == "uniform":
        return rng.integers(0, n_keys, size=count, dtype=np.int64)
    if dist == "zipf":
        cdf = zipf_cdf(n_keys, float(spec["s"]))
        ranks = np.searchsorted(cdf, rng.random(count), side="left")
        ranks = np.minimum(ranks, n_keys - 1).astype(np.int64)
        return scramble(ranks, n_keys) if spec.get("scrambled") else ranks
    if dist == "hotset":
        hot = int(spec["hot_keys"])
        is_hot = rng.random(count) < float(spec["hot_share"])
        return np.where(
            is_hot,
            rng.integers(0, hot, size=count, dtype=np.int64),
            rng.integers(hot, n_keys, size=count, dtype=np.int64),
        )
    raise ValueError(f"unknown key distribution {dist!r}")


def hottest_keys(spec: dict, n_keys: int, k: int) -> np.ndarray:
    """Ids of the k most popular keys under `spec` (uniform has none that
    stand out: the first k ids)."""
    ranks = np.arange(min(k, n_keys), dtype=np.int64)
    if spec.get("distribution") == "zipf" and spec.get("scrambled"):
        return scramble(ranks, n_keys)
    return ranks


# ---- sizes and arrivals: fixed multisets, permuted by the seed ----------------


def apportion(shares: dict, count: int) -> list:
    """[(value, how many)] with exact proportions (largest remainders)."""
    total = float(sum(shares.values()))
    raw = [(v, count * w / total) for v, w in shares.items()]
    out = [[v, int(math.floor(x))] for v, x in raw]
    left = count - sum(c for _, c in out)
    order = sorted(range(len(raw)), key=lambda i: raw[i][1] - out[i][1],
                   reverse=True)
    for i in order[:left]:
        out[i][1] += 1
    return [(v, c) for v, c in out]


def shared_out(shares: dict, count: int, rng: np.random.Generator) -> np.ndarray:
    """`count` values in the exact proportions of `shares` (value -> weight,
    the values whole numbers as a data file's keys or as numbers), the same
    multiset for every seed, in an order the seed's stream gives."""
    parts = apportion({int(v): float(w) for v, w in shares.items()}, count)
    out = np.concatenate([np.full(c, v, dtype=np.int64) for v, c in parts])
    rng.shuffle(out)
    return out


def call_sizes(spec, count: int, rng: np.random.Generator) -> np.ndarray:
    if isinstance(spec, dict):
        sizes = shared_out(spec, count, rng)
    else:
        sizes = np.full(count, int(spec), dtype=np.int64)
    if sizes.min() < 1 or sizes.max() > wire.MAX_ITEMS_PER_CALL:
        raise ValueError("items_per_call outside 1..1000")
    return sizes


def due_times(spec: dict, rate: float, seconds: float,
              rng: np.random.Generator) -> np.ndarray:
    """Seconds from the window's opening at which each open-loop call is
    due. Poisson: n = rate*seconds gaps that are the exponential's
    quantiles (the same multiset for every seed), shuffled, summed."""
    kind = spec.get("kind", "poisson")
    n = int(round(rate * seconds))
    if n < 1:
        raise ValueError("the window holds no call at this rate")
    if kind == "poisson":
        u = (np.arange(n, dtype=np.float64) + 0.5) / n
        gaps = -np.log1p(-u) / rate
        gaps *= seconds / gaps.sum()  # the n-th call falls due at the close
        rng.shuffle(gaps)
        return np.cumsum(gaps) - gaps[0] * 0.5
    if kind == "bursts":
        per, every = int(spec["calls"]), float(spec["every_ms"]) / 1000.0
        n_bursts = max(int(seconds / every), 1)
        return np.repeat(np.arange(n_bursts) * every, per)[:max(n, 1)]
    raise ValueError(f"unknown arrivals kind {kind!r}")


# ---- the plan -----------------------------------------------------------------


@dataclass
class Plan:
    """Calls made before the window opens. ``keys[i]`` holds call i's key
    ids, ``blobs[i]`` its encoded request. Closed loop: ``caller_of[i]`` is
    the caller whose pool holds call i. Open loop: ``due[i]`` seconds.
    ``behaviors[i]`` and ``hits[i]`` are its items' flags and hits."""

    loop: str
    keys: list
    behaviors: list
    hits: list
    blobs: list
    caller_of: np.ndarray
    due: np.ndarray
    callers: int


def build_plan(traffic: dict, keyspace: Keyspace, seed: int,
               seconds: float) -> Plan:
    loop = traffic["loop"]
    if loop == "closed":
        callers = int(traffic["callers"])
        n_calls = callers * int(traffic.get("pool_calls", 64))
        due = np.zeros(0)
    elif loop == "open":
        callers = 0
        rate = float(traffic["rate_calls_per_s"])
        due = due_times(traffic.get("arrivals", {}), rate, seconds,
                        rng_for(seed, 3))
        n_calls = len(due)
    else:
        raise ValueError(f"unknown loop kind {loop!r}")
    sizes = call_sizes(traffic["items_per_call"], n_calls, rng_for(seed, 2))
    flat = draw_keys(traffic.get("keys", {}), keyspace.n, int(sizes.sum()),
                     rng_for(seed, 1))
    keys = np.split(flat, np.cumsum(sizes)[:-1])
    shares = traffic.get("behavior_shares")
    if shares:
        beh_flat = shared_out(
            {behavior_bits(s["behavior"]) | keyspace.behavior: s["share"]
             for s in shares}, len(flat), rng_for(seed, 4))
    else:
        beh_flat = np.full(len(flat), keyspace.behavior, dtype=np.int64)
    if keyspace.key_flags:
        beh_flat = beh_flat | keyspace.flags[flat]
    behaviors = np.split(beh_flat, np.cumsum(sizes)[:-1])
    spec = traffic.get("hits", 1)
    if isinstance(spec, dict):
        hits_flat = shared_out(spec, len(flat), rng_for(seed, 5))
        if hits_flat.min() < 1:
            raise ValueError("hits under 1: a window's item takes something")
    else:
        hits_flat = np.full(len(flat), int(spec), dtype=np.int64)
    hits = np.split(hits_flat, np.cumsum(sizes)[:-1])
    blobs = [
        wire.encode_call([
            keyspace.request(k, int(h), behavior=int(b))
            for k, h, b in zip(ks, hs, bs)
        ])
        for ks, hs, bs in zip(keys, hits, behaviors)
    ]
    caller_of = (
        np.arange(n_calls) % callers if loop == "closed"
        else np.zeros(n_calls, dtype=np.int64)
    )
    return Plan(loop, keys, behaviors, hits, blobs, caller_of, due, callers)
