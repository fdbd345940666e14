"""The comparison that decides ``correct``: the served path held to the
plain reference (reference/oracle.py), by integer equality throughout.

Three stages, all on what the timed path's own server answered:

1. before the window: sequential calls of the cell's own mix with a
   pinned clock, every answer equal to the reference's (``Sequential``);
2. in the window: every response kept; with one hit per item the
   order-free invariants of a token bucket are exact (``check_window``):
   inside one generation of a key (one ``reset_time``) the accepted hits
   carry the remaining values start-1, start-2, ... start-n, each once;
   an OVER_LIMIT answer carries 0 and only occurs in a generation that is
   used up; every ``limit`` echoes the request;
3. after the window: ``hits=0`` probes equal what was left minus the
   accepted hits (``check_probes``).

The reference has no capacity, the table has: it is set-associative and
evicts inside a group. A key that shows a new generation while its old
one had not expired was evicted. That is counted, and held to three times
what the table's geometry lets one expect among the keys a run looks at
(``eviction_allowance``); inside every generation the
count stays exact.

Every number compared is printed beside its limit by ``Verdict.lines``.
"""

from __future__ import annotations

import copy
import math
from dataclasses import dataclass, field

import numpy as np

from benchmarks.reference.oracle import (
    OVER_LIMIT,
    TOKEN_BUCKET,
    UNDER_LIMIT,
    Reference,
)

PLAIN_BEHAVIORS = (0,)  # what the order-free invariants model


@dataclass
class Verdict:
    """Named counts, each with its limit; correct iff none is over."""

    rows: list = field(default_factory=list)
    examples: list = field(default_factory=list)

    def add(self, name: str, value, limit, example: str = "") -> None:
        self.rows.append((name, int(value), int(limit)))
        if value > limit and example and len(self.examples) < 8:
            self.examples.append(f"{name}: {example}")

    @property
    def correct(self) -> bool:
        return all(v <= lim for _, v, lim in self.rows)

    def lines(self) -> list:
        out = [
            f"check {name}: {v} (limit {lim}) {'ok' if v <= lim else 'FAIL'}"
            for name, v, lim in self.rows
        ]
        return out + [f"check example: {e}" for e in self.examples]


# ---- stage 1: sequential, against the reference -------------------------------


class Sequential:
    """Feeds the same requests in the same order to the server and to the
    reference. Keys are independent in the reference, so a preloaded key's
    history is replayed into it when the key is first needed
    (``history``), not for all keys up front.

    The reference has no capacity. Where the server's answer for a key the
    reference holds differs from the reference's and equals a new key's
    answer, the table evicted that key: it is counted (``evicted``, held
    to the geometry's allowance by the caller) and the reference forgets
    the key too. Any other difference is a mismatch."""

    def __init__(self, send, history=None):
        self.send = send  # list[Request] -> list of response tuples
        self.ref = Reference()
        self.history = history or (lambda key_id: [])
        self.known = set()
        self.evicted = set()
        self.items = 0
        self.mismatches = 0
        self.examples = []

    def ensure(self, key_id: int) -> None:
        if key_id not in self.known:
            self.known.add(key_id)
            for req, now_ms in self.history(key_id):
                self.ref.get_rate_limits([req], now_ms)

    def _answer(self, key_id: int, req, got, now_ms: int) -> tuple:
        """The reference's answer, after adopting an eviction if `got`
        shows one."""
        held = self.ref.cache.get(req.hash_key())
        want = self.ref.get_rate_limits([copy.copy(req)], now_ms)[0].as_tuple()
        if got == want or held is None:
            return want
        fresh = Reference()
        as_new = fresh.get_rate_limits([copy.copy(req)], now_ms)[0].as_tuple()
        if got != as_new:
            return want
        self.evicted.add(int(key_id))
        self.ref.cache[req.hash_key()] = fresh.cache[req.hash_key()]
        return as_new

    def call(self, what: str, key_ids, reqs, now_ms: int):
        for k in key_ids:
            self.ensure(int(k))
        got = [tuple(g) for g in self.send(reqs)]
        if len(got) != len(reqs):
            self.mismatches += len(reqs)
            self.examples.append(
                f"{what}: {len(got)} responses for {len(reqs)} requests")
            return got
        for k, r, g in zip(key_ids, reqs, got):
            w = self._answer(k, r, g, now_ms)
            self.items += 1
            if g != w:
                self.mismatches += 1
                if len(self.examples) < 5:
                    self.examples.append(
                        f"{what} key={r.unique_key} hits={r.hits}"
                        f": got {g} want {w}")
        return got

    def token_state(self, key_id: int, keyspace):
        """(remaining, reset_time, over-limit is sticky) the reference
        holds for a token key, or None."""
        self.ensure(int(key_id))
        item = self.ref.cache.get(keyspace.request(key_id, 0).hash_key())
        if item is None or item.algorithm != TOKEN_BUCKET:
            return None
        return (item.value.remaining, item.expire_at,
                item.value.status == OVER_LIMIT)


# ---- capacity -------------------------------------------------------------------


def _poisson(m: float, upto: int):
    p = math.exp(-m)
    for k in range(upto):
        yield k, p
        p *= m / (k + 1)


def evictable_share(keys: int, groups: int, ways: int) -> float:
    """Share of keys that sit in a group holding more than `ways` of them
    when `keys` are spread evenly at random over `groups`: for a Poisson
    count X with mean m = keys/groups, E[X; X > ways] / m = P(X >= ways).
    No more keys than these can ever show an eviction."""
    return max(1.0 - sum(p for _, p in _poisson(keys / groups, ways)), 0.0)


def lost_share(keys: int, groups: int, ways: int) -> float:
    """Share of keys that are not resident once all `keys` are loaded:
    E[max(X - ways, 0)] / m. A run sees such a key as evicted when it
    touches it."""
    m = keys / groups
    return sum((k - ways) * p for k, p in _poisson(m, ways + 200) if k > ways) / m


def eviction_allowance(observed_keys: int, keys: int, groups: int, ways: int) -> int:
    """Keys that may show an eviction among the `observed_keys` a run
    looked at. Expected: the observed keys that were not resident, and the
    rivals they displaced that were looked at again, which grows with the
    share f of the keyspace observed: observed * lost_share / (1 - f). The
    allowance is three times that (on the chip sound runs read the
    expected count itself, PERF.md), never more than the observed keys in
    over-full groups plus four standard deviations, plus 2."""
    f = min(observed_keys / keys, 1.0)
    expected = observed_keys * lost_share(keys, groups, ways) / max(1.0 - f, 1e-9)
    share = evictable_share(keys, groups, ways)
    top = observed_keys * share
    top += 4.0 * math.sqrt(top * (1.0 - share))
    return int(math.ceil(min(3.0 * expected, top))) + 2


# ---- stages 2 and 3 ---------------------------------------------------------------


@dataclass
class Items:
    """Flat per-item arrays of a set of answered calls."""

    key: np.ndarray
    status: np.ndarray
    limit: np.ndarray
    remaining: np.ndarray
    reset_time: np.ndarray
    valid: np.ndarray  # the call returned and the item carries no error
    behavior: np.ndarray


def _first(mask: np.ndarray, items: Items, what: str) -> str:
    i = int(np.argmax(mask))
    return (f"{what} key={int(items.key[i])} status={int(items.status[i])} "
            f"limit={int(items.limit[i])} remaining={int(items.remaining[i])} "
            f"reset_time={int(items.reset_time[i])}")


@dataclass
class Carried:
    """What the reference holds for each token key when the window opens
    (arrays over the keyspace; ``reset_time`` -1 where it holds nothing)."""

    remaining: np.ndarray
    reset_time: np.ndarray
    sticky_over: np.ndarray

    @classmethod
    def empty(cls, n: int) -> "Carried":
        return cls(np.zeros(n, np.int64), np.full(n, -1, np.int64),
                   np.zeros(n, bool))


class WindowCheck:
    """Order-free invariants over the window's items, then the probes."""

    def __init__(self, keyspace, carried: Carried, uncertain: np.ndarray):
        self.ks = keyspace
        self.carried = carried
        self.uncertain = uncertain  # bool over keys: in a call that failed
        # generations seen in the window, sorted by (key, reset_time)
        self.g_key = np.zeros(0, np.int64)
        self.g_reset = np.zeros(0, np.int64)
        self.g_accepted = np.zeros(0, np.int64)
        self.g_over = np.zeros(0, bool)
        self.evicted = set()

    def _initial(self, key: np.ndarray, reset: np.ndarray) -> np.ndarray:
        c = self.carried
        return np.where(c.reset_time[key] == reset, c.remaining[key],
                        self.ks.limit)

    def check_window(self, it: Items, v: Verdict) -> None:
        ks = self.ks
        ok = it.valid
        plain = np.isin(it.behavior, PLAIN_BEHAVIORS)
        tok = ks.is_token(it.key)
        v.add("window.limit_not_echoed", np.sum(ok & (it.limit != ks.limit)), 0,
              _first(ok & (it.limit != ks.limit), it, "limit"))
        bad_range = ok & ((it.remaining < 0) | (it.remaining > ks.limit)
                          | ~np.isin(it.status, (UNDER_LIMIT, OVER_LIMIT)))
        v.add("window.out_of_range", np.sum(bad_range), 0,
              _first(bad_range, it, "range"))
        over_nonzero = ok & plain & (it.status == OVER_LIMIT) & (it.remaining != 0)
        v.add("window.over_limit_with_remaining", np.sum(over_nonzero), 0,
              _first(over_nonzero, it, "over"))
        sel = ok & plain & tok
        key, rst = it.key[sel], it.reset_time[sel]
        rem, st = it.remaining[sel], it.status[sel]
        order = np.lexsort((rem, rst, key))
        key, rst, rem, st = key[order], rst[order], rem[order], st[order]
        n = len(key)
        if not n:
            v.add("window.token_generations_not_exact", 0, 0)
            v.add("window.over_limit_before_used_up", 0, 0)
            return
        new = np.ones(n, dtype=bool)
        new[1:] = (key[1:] != key[:-1]) | (rst[1:] != rst[:-1])
        gid = np.cumsum(new) - 1
        starts = np.nonzero(new)[0]
        g_key, g_reset = key[starts], rst[starts]
        n_gen = len(starts)
        acc = st == UNDER_LIMIT
        g_acc = np.bincount(gid[acc], minlength=n_gen)
        g_over = np.bincount(gid[~acc], minlength=n_gen) > 0
        initial = self._initial(g_key, g_reset)
        certain = ~self.uncertain[g_key]
        # accepted remaining values, ascending inside each generation
        a_gid, a_rem = gid[acc], rem[acc]
        lo = np.full(n_gen, 0, np.int64)
        hi = np.full(n_gen, -1, np.int64)
        steps_off = np.zeros(n_gen, np.int64)
        if len(a_gid):
            a_new = np.ones(len(a_gid), dtype=bool)
            a_new[1:] = a_gid[1:] != a_gid[:-1]
            a_starts = np.nonzero(a_new)[0]
            a_ends = np.append(a_starts[1:], len(a_gid)) - 1
            lo[a_gid[a_starts]] = a_rem[a_starts]
            hi[a_gid[a_starts]] = a_rem[a_ends]
            d = np.diff(a_rem)
            inside = ~a_new[1:]
            # certain keys: steps of exactly 1; keys of a failed call: no repeat
            wrong = inside & np.where(certain[a_gid[1:]], d != 1, d == 0)
            steps_off = np.bincount(a_gid[1:][wrong], minlength=n_gen)
        has = g_acc > 0
        bad_seq = has & (
            (steps_off > 0)
            | np.where(certain,
                       (lo != initial - g_acc) | (hi != initial - 1),
                       (lo < 0) | (hi > initial - 1))
        )
        bad_over = g_over & certain & (initial - g_acc != 0)

        def gen(i):
            return (f"key={int(g_key[i])} reset_time={int(g_reset[i])} "
                    f"start={int(initial[i])} accepted={int(g_acc[i])} "
                    f"lowest={int(lo[i])} highest={int(hi[i])}")

        v.add("window.token_generations_not_exact", np.sum(bad_seq), 0,
              gen(int(np.argmax(bad_seq))))
        v.add("window.over_limit_before_used_up", np.sum(bad_over), 0,
              gen(int(np.argmax(bad_over))))
        self.g_key, self.g_reset = g_key, g_reset
        self.g_accepted, self.g_over = g_acc, g_over
        # evictions: a generation made while the previous one was alive
        dur = ks.duration_ms
        same = g_key[1:] == g_key[:-1]
        early = same & (g_reset[1:] - dur <= g_reset[:-1])
        first = np.ones(n_gen, dtype=bool)
        first[1:] = ~same
        c_reset = self.carried.reset_time[g_key]
        early_first = (first & (c_reset >= 0) & (g_reset != c_reset)
                       & (g_reset - dur <= c_reset))
        self.evicted.update(g_key[1:][early].tolist())
        self.evicted.update(g_key[early_first].tolist())

    def check_probes(self, it: Items, v: Verdict) -> None:
        """hits=0 probes sent after the window."""
        ks = self.ks
        tok = ks.is_token(it.key)
        c = self.carried
        probed = np.isin(self.g_key, it.key)
        gens = {
            (int(k), int(r)): (int(a), bool(o))
            for k, r, a, o in zip(self.g_key[probed], self.g_reset[probed],
                                  self.g_accepted[probed], self.g_over[probed])
        }
        last = {}
        for (k, r) in gens:
            last[k] = max(last.get(k, -1), r)
        bad = 0
        example = ""
        v.add("probe.failed", np.sum(~it.valid), 0)
        for i in np.nonzero(it.valid)[0].tolist():
            k = int(it.key[i])
            got = (int(it.status[i]), int(it.limit[i]), int(it.remaining[i]),
                   int(it.reset_time[i]))
            c_rem, c_reset = int(c.remaining[k]), int(c.reset_time[k])
            if not tok[i]:
                want_ok = got[1] == ks.limit and 0 <= got[2] <= ks.limit
                want = "0 <= remaining <= burst, limit echoed"
            elif self.uncertain[k]:
                continue
            else:
                r = got[3]
                if (k, r) in gens or c_reset == r:
                    n_acc, over = gens.get((k, r), (0, False))
                    start = c_rem if c_reset == r else ks.limit
                    sticky = over or (c_reset == r and bool(c.sticky_over[k]))
                    want = (OVER_LIMIT if sticky else UNDER_LIMIT, ks.limit,
                            start - n_acc, r)
                else:  # a bucket the probe itself made
                    prev = max(last.get(k, -1), c_reset)
                    if prev >= 0 and r - ks.duration_ms <= prev:
                        self.evicted.add(k)
                    want = (UNDER_LIMIT, ks.limit, ks.limit, r)
                want_ok = got == want
            if not want_ok:
                bad += 1
                example = example or f"key={k}: got {got} want {want}"
        v.add("probe.mismatches", bad, 0, example)

    def check_evictions(self, observed_keys: int, groups: int, ways: int,
                        v: Verdict) -> None:
        v.add("evicted_keys", len(self.evicted),
              eviction_allowance(observed_keys, self.ks.n, groups, ways),
              f"first keys {sorted(self.evicted)[:5]}")
