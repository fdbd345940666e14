"""The comparison that decides ``correct``: the served path held to the
plain reference (reference/oracle.py), by integer equality throughout.

Four stages, all on what the timed path's own server answered or saved:

1. before the window: sequential calls of the cell's own mix with a
   pinned clock, every answer equal to the reference's (``Sequential``);
2. in the window: every response kept; the order-free invariants of a
   token bucket are exact (``check_window``), whatever an item's hits h
   and whether it carries DRAIN_OVER_LIMIT or RESET_REMAINING: inside one
   generation of a key (one ``reset_time``) the accepted items, each the
   stretch (remaining, remaining + h], tile (low, start] with no gap and
   no overlap (with one hit each: start-1, start-2, ... start-n, each
   once); an OVER_LIMIT answer carries a remaining under its h that the
   generation passed through (0 once it was drained) and consumes
   nothing; a refusal that carried DRAIN_OVER_LIMIT shows 0 and leaves 0;
   an item that carried RESET_REMAINING is answered the full limit with
   ``reset_time`` 0 (it found a bucket, took nothing and removed it) or
   as the first of a generation (it found none); every ``limit`` echoes
   the request;
3. after the window: ``hits=0`` probes equal what was left minus the
   accepted hits, 0 where the generation was drained (``check_probes``);
4. after the exit, for a configuration with ``"shutdown": {"saved":
   "checked"}``: the snapshot the server's Loader wrote (snapshot.py) holds
   no key that is not of the keyspace (``save.keys_unknown``), every probed
   token key (``save.keys_missing``) and for each the ``limit``,
   ``duration``, ``remaining`` and expiry its probe answered
   (``save.rows_differ``), which stage 3 has held to the reference: every
   acknowledged hit is in the checkpoint (``check_saved``).

A configuration preloaded by a snapshot (``"preload": {"via": "snapshot"}``)
has a row before stage 1, ``load.keys_not_resident``: its keys less the
slots ``/debug/table`` counts in use after the Load, against what the
geometry lets one expect (``resident_allowance``). Stage 1 then holds the
Load as it holds a gRPC preload: its history is the same request at the
same pinned clock, so a key the Load dropped or changed answers unlike the
reference.

What the rows model is behaviour 0, DRAIN_OVER_LIMIT, RESET_REMAINING and
their union at any hits >= 1 (``modelled``); GLOBAL items have rows of
their own (below) and DURATION_IS_GREGORIAN is outside the reference.
Leaky keys are held to their range, the echo of ``limit``, a refusal's
remaining under its hits, and a RESET_REMAINING answer to burst less its
hits.

The reference has no capacity, the table has: it is set-associative and
evicts inside a group. A key that shows a new generation while its old
one had not expired was evicted. That is counted, and held to three times
what the table's geometry lets one expect among the keys a run looks at
(``eviction_allowance``); inside every generation the
count stays exact. A RESET_REMAINING answer that removed the key's bucket
pays for one generation made while the old one lived: a key counts as
evicted only where the window shows more such generations than removals.
The check keeps no order, so it cannot say which removal came before which
generation: what that excuses is a bucket the table forgot of a key that
was also reset in the window and whose removal no new generation followed.
One call stamps one time on its items, so a key reset and hit again twice
in one millisecond shows two generations under one ``reset_time``: the
accepted items of such a group have to split into that many tilings, each
from the full limit (``_chains``), and each further one is a generation
made while the old one lived.

A configuration whose guarantee is eventual (consistency.py) answers
from copies that are reconciled in the background. Its items (those whose
behaviour carries GLOBAL) are held in the window to what any copy may
answer (``check_window_eventual``: rows ``window.global_*``), and after
quiescence every copy to the reference's totals, exactly
(``check_probes_eventual``: rows ``probe.global_*``). Two copies may each
make a key's bucket before they have met, a few milliseconds apart; the
copies then keep one. Such generations of a key, no further apart than the
configuration says copies take to join, count as one bucket. Plain items
keep the rows above.

Every number compared is printed beside its limit by ``Verdict.lines``.
"""

from __future__ import annotations

import copy
import math
from collections import Counter
from dataclasses import dataclass, field

import numpy as np

from benchmarks.reference.oracle import (
    DRAIN_OVER_LIMIT,
    GLOBAL,
    OVER_LIMIT,
    RESET_REMAINING,
    TOKEN_BUCKET,
    UNDER_LIMIT,
    Reference,
)


def modelled(behavior: np.ndarray) -> np.ndarray:
    """Items whose flags the order-free invariants model."""
    return (behavior & ~(DRAIN_OVER_LIMIT | RESET_REMAINING)) == 0


def is_global(behavior: np.ndarray) -> np.ndarray:
    """Items answered from a copy of their bucket, reconciled later."""
    return (behavior & GLOBAL) != 0


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

    def as_dict(self) -> dict:
        """{name: [value, limit]}, for the result line."""
        return {name: [v, lim] for name, v, lim in self.rows}

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


def resident_allowance(keys: int, groups: int, ways: int) -> int:
    """Keys that may find no slot when all `keys` are loaded into an empty
    table: those over `ways` in their group, `keys * lost_share` expected,
    plus six standard deviations of that sum over the groups, plus 2 (ten
    seeds on the chip spread by 1.4 of the model's deviation, PERF.md §2)."""
    m = keys / groups
    over = [(k - ways, p) for k, p in _poisson(m, ways + 200) if k > ways]
    mean = sum(o * p for o, p in over)
    var = sum(o * o * p for o, p in over) - mean * mean
    return int(math.ceil(groups * mean + 6.0 * math.sqrt(groups * var))) + 2


def check_load(table: dict, keys: int, v: Verdict) -> None:
    """A table filled by a Load, as ``/debug/table`` (or the tier of it that
    holds the keys) reports it once the server is healthy."""
    v.add("load.keys_not_resident", max(keys - int(table["live"]), 0),
          resident_allowance(keys, table["groups"], table["ways"]),
          f"{table['live']} slots in use for {keys} keys")


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


def generations(key: np.ndarray, reset: np.ndarray, then=()) -> tuple:
    """Items grouped into generations, one per (key, reset_time): the order
    that sorts them by key, reset_time and the columns `then`; each sorted
    item's generation; and where each generation starts in that order."""
    order = np.lexsort(tuple(then) + (reset, key))
    key, reset = key[order], reset[order]
    new = np.ones(len(key), dtype=bool)
    new[1:] = (key[1:] != key[:-1]) | (reset[1:] != reset[:-1])
    return order, np.cumsum(new) - 1, np.nonzero(new)[0]


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
    hits: np.ndarray = None  # what each item asked for; one where not given

    def __post_init__(self):
        if self.hits is None:
            self.hits = np.ones(len(self.key), dtype=np.int64)


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


def _chains(rem: list, hits: list, start: int):
    """Splits accepted items (``remaining``, hits) into tilings that each
    begin at `start` and step down with no gap: the lowest value of each,
    or None where they do not split so. An item begins a tiling where its
    stretch ends at `start` and otherwise continues the one that stands
    where its stretch ends; tilings that stand at one value are alike, so
    taking the items from the top down decides it."""
    stands = Counter()
    for r, h in sorted(zip(rem, hits), key=lambda x: -(x[0] + x[1])):
        top = r + h
        if top != start:
            if not stands[top]:
                return None
            stands[top] -= 1
        stands[r] += 1
    return sorted(stands.elements())


class WindowCheck:
    """Order-free invariants over the window's items, then the probes."""

    def __init__(self, keyspace, carried: Carried, uncertain: np.ndarray):
        self.ks = keyspace
        self.carried = carried
        self.uncertain = uncertain  # bool over keys: in a call that failed
        # generations seen in the window, sorted by (key, reset_time): what
        # each took (the sum of its accepted hits), its OVER_LIMIT answers
        # that showed 0, and whether a refusal that drains is among them
        self.g_key = np.zeros(0, np.int64)
        self.g_reset = np.zeros(0, np.int64)
        self.g_taken = np.zeros(0, np.int64)
        self.g_over_at_zero = np.zeros(0, np.int64)
        self.g_drain = np.zeros(0, bool)
        self.split = {}  # (key, reset_time) -> the lows of its several tilings
        # per key: RESET_REMAINING answers that removed its bucket, and
        # generations made while the old one lived
        self.removed = np.zeros(keyspace.n, np.int64)
        self.early = {}
        self.evicted = set()
        self.counted = {}  # what the window held, for the run's last line

    def _initial(self, key: np.ndarray, reset: np.ndarray) -> np.ndarray:
        c = self.carried
        return np.where(c.reset_time[key] == reset, c.remaining[key],
                        self.ks.limit)

    def check_window(self, it: Items, v: Verdict) -> None:
        ks = self.ks
        ok = it.valid
        plain = modelled(it.behavior)
        tok = ks.is_token(it.key)
        over = it.status == OVER_LIMIT
        drains = plain & ((it.behavior & DRAIN_OVER_LIMIT) != 0)
        resets = plain & ((it.behavior & RESET_REMAINING) != 0)
        v.add("window.limit_not_echoed", np.sum(ok & (it.limit != ks.limit)), 0,
              _first(ok & (it.limit != ks.limit), it, "limit"))
        bad_range = ok & ((it.remaining < 0) | (it.remaining > ks.limit)
                          | ~np.isin(it.status, (UNDER_LIMIT, OVER_LIMIT)))
        v.add("window.out_of_range", np.sum(bad_range), 0,
              _first(bad_range, it, "range"))
        # a refusal shows what was left, and that is less than it asked for
        over_enough = ok & plain & over & ((it.remaining < 0)
                                           | (it.remaining >= it.hits))
        v.add("window.over_limit_with_remaining", np.sum(over_enough), 0,
              _first(over_enough, it, "over"))
        # RESET_REMAINING: the bucket removed (the full limit, no reset_time),
        # or none found and the item the first of a generation; a leaky
        # bucket is refilled to its burst and then takes the hits
        removal = (resets & tok & ~over & (it.remaining == ks.limit)
                   & (it.reset_time == 0))
        first = ~over & (it.remaining == ks.limit - it.hits)
        not_fresh = ok & resets & ~removal & ~(first & (~tok | (it.reset_time != 0)))
        self.removed = np.bincount(it.key[ok & removal], minlength=ks.n)
        drain_left = ok & drains & over & (it.remaining != 0)
        self.counted = {
            "items": int(np.sum(ok)),
            "items_hits_over_1": int(np.sum(ok & (it.hits > 1))),
            "items_drain": int(np.sum(ok & drains)),
            "items_reset": int(np.sum(ok & resets)),
            "refused": int(np.sum(ok & plain & over)),
            "refused_with_remainder": int(np.sum(ok & plain & over & (it.remaining > 0))),
            "reset_removed_bucket": int(np.sum(ok & removal)),
        }
        sel = ok & plain & tok & ~removal
        key, rst = it.key[sel], it.reset_time[sel]
        rem, st = it.remaining[sel], it.status[sel]
        hit, drn = it.hits[sel], drains[sel]

        def flag_rows(stood=0, example=""):
            v.add("window.drain_left_remaining", np.sum(drain_left) + stood, 0,
                  _first(drain_left, it, "drain") if drain_left.any() else example)
            v.add("window.reset_not_fresh", np.sum(not_fresh), 0,
                  _first(not_fresh, it, "reset"))

        if not len(key):
            v.add("window.token_generations_not_exact", 0, 0)
            v.add("window.over_limit_before_used_up", 0, 0)
            flag_rows()
            return
        order, gid, starts = generations(key, rst, then=(rem,))
        key, rst, rem, st = key[order], rst[order], rem[order], st[order]
        hit, drn = hit[order], drn[order]
        g_key, g_reset = key[starts], rst[starts]
        n_gen = len(starts)
        acc = st == UNDER_LIMIT
        g_acc = np.bincount(gid[acc], minlength=n_gen)
        g_taken = np.bincount(gid[acc], weights=hit[acc],
                              minlength=n_gen).astype(np.int64)
        initial = self._initial(g_key, g_reset)
        certain = ~self.uncertain[g_key]
        # accepted items, ascending by remaining inside each generation: the
        # stretch (remaining, remaining + hits] each took
        a_gid, a_rem, a_top = gid[acc], rem[acc], rem[acc] + hit[acc]
        lo = initial.copy()  # where the generation stands at the end
        top = np.full(n_gen, -1, np.int64)
        steps_off = np.zeros(n_gen, np.int64)
        a_first = np.zeros(n_gen, np.int64)
        if len(a_gid):
            a_new = np.ones(len(a_gid), dtype=bool)
            a_new[1:] = a_gid[1:] != a_gid[:-1]
            a_starts = np.nonzero(a_new)[0]
            a_ends = np.append(a_starts[1:], len(a_gid)) - 1
            a_first[a_gid[a_starts]] = a_starts
            lo[a_gid[a_starts]] = a_rem[a_starts]
            top[a_gid[a_starts]] = a_top[a_ends]
            gap = a_rem[1:] - a_top[:-1]
            inside = ~a_new[1:]
            # certain keys: each stretch ends where the next begins; keys of
            # a failed call: no overlap
            wrong = inside & np.where(certain[a_gid[1:]], gap != 0, gap < 0)
            steps_off = np.bincount(a_gid[1:][wrong], minlength=n_gen)
        has = g_acc > 0
        bad_seq = has & (
            (steps_off > 0)
            | np.where(certain, top != initial, (lo < 0) | (top > initial))
        )
        # several generations under one reset_time: made in one millisecond,
        # a RESET_REMAINING between them
        extra = np.zeros(n_gen, np.int64)
        made_here = self.carried.reset_time[g_key] != g_reset
        for g in np.nonzero(bad_seq & certain & made_here
                            & (self.removed[g_key] > 0))[0].tolist():
            a, b = int(a_first[g]), int(a_first[g]) + int(g_acc[g])
            lows = _chains(a_rem[a:b].tolist(), (a_top[a:b] - a_rem[a:b]).tolist(),
                           ks.limit)
            if lows is not None:
                bad_seq[g] = False
                extra[g] = len(lows) - 1
                lo[g] = lows[0]
                self.split[(int(g_key[g]), int(g_reset[g]))] = lows
        # a refusal shows a value its generation passed through: where it
        # began or what an accepted item left; 0 only where the accepted hits
        # add up to the start (with one hit each: as many as the start) or a
        # refusal drained it
        o_gid, o_rem, o_hit, o_drn = gid[~acc], rem[~acc], hit[~acc], drn[~acc]
        g_drain = np.bincount(o_gid[o_drn], minlength=n_gen) > 0
        emptied = np.where(extra > 0, lo == 0, g_taken == initial) | g_drain
        span = ks.limit + 2
        passed = np.isin(o_gid * span + np.clip(o_rem, -1, ks.limit),
                         a_gid * span + np.clip(a_rem, -1, ks.limit))
        passed = np.where(o_rem == 0, emptied[o_gid],
                          passed | (o_rem == initial[o_gid]))
        bad_over = np.bincount(o_gid[~passed & certain[o_gid]], minlength=n_gen) > 0
        # a refusal that drains left nothing, and the one that emptied the
        # generation asked for more than it had fallen to
        most = np.zeros(n_gen, np.int64)
        np.maximum.at(most, o_gid[o_drn], o_hit[o_drn])
        g_drained = g_drain & (lo > 0)
        stood = g_drained & certain & (most <= lo)

        def gen(i):
            return (f"key={int(g_key[i])} reset_time={int(g_reset[i])} "
                    f"start={int(initial[i])} accepted={int(g_acc[i])} "
                    f"took={int(g_taken[i])} lowest={int(lo[i])} "
                    f"highest_stretch_ends={int(top[i])}")

        v.add("window.token_generations_not_exact", np.sum(bad_seq), 0,
              gen(int(np.argmax(bad_seq))))
        v.add("window.over_limit_before_used_up", np.sum(bad_over), 0,
              gen(int(np.argmax(bad_over))))
        flag_rows(np.sum(stood), gen(int(np.argmax(stood))))
        self.g_key, self.g_reset = g_key, g_reset
        self.g_taken, self.g_drain = g_taken, g_drained
        self.g_over_at_zero = np.bincount(o_gid[o_rem == 0], minlength=n_gen)
        self._note_evictions(g_key, g_reset, extra=extra)
        self.counted.update(
            generations=int(n_gen + extra.sum()),
            generations_after_reset=int(sum(
                min(n, int(self.removed[k])) for k, n in self.early.items())),
            generations_drained=int(np.sum(g_drained)),
            generations_under_one_reset_time=int(extra.sum()),
        )

    def _note_evictions(self, g_key: np.ndarray, g_reset: np.ndarray,
                        holds_carried: np.ndarray = None, slack_ms: int = 0,
                        extra: np.ndarray = None) -> None:
        """Evictions among buckets sorted by (key, reset_time): one made
        while the previous one, or the carried one, was alive, beyond those
        that a RESET_REMAINING answer's removal paid for (``removed``).
        Where several generations count as one bucket, `g_reset` is the
        earliest of them (the copies may have kept that one) and
        `holds_carried` says the carried generation is among them.
        `slack_ms` before its time is up a bucket may be gone already
        (``check_window_eventual``). `extra`: further generations a row
        stands for, made in its own millisecond."""
        dur = self.ks.duration_ms - slack_ms
        same = g_key[1:] == g_key[:-1]
        early = same & (g_reset[1:] - dur <= g_reset[:-1])
        first = np.ones(len(g_key), dtype=bool)
        first[1:] = ~same
        c_reset = self.carried.reset_time[g_key]
        if holds_carried is None:
            holds_carried = g_reset == c_reset
        early_first = (first & (c_reset >= 0) & ~holds_carried
                       & (g_reset - dur <= c_reset))
        made = [g_key[1:][early], g_key[early_first]]
        if extra is not None:
            made.append(np.repeat(g_key, extra))
        keys, counts = np.unique(np.concatenate(made), return_counts=True)
        self.early = dict(zip(keys.tolist(), counts.tolist()))
        self.evicted.update(keys[counts > self.removed[keys]].tolist())

    def check_probes(self, it: Items, v: Verdict) -> None:
        """hits=0 probes sent after the window."""
        ks = self.ks
        tok = ks.is_token(it.key)
        c = self.carried
        probed = np.isin(self.g_key, it.key)
        gens = {
            (int(k), int(r)): (int(t), int(z), bool(d))
            for k, r, t, z, d in zip(
                self.g_key[probed], self.g_reset[probed], self.g_taken[probed],
                self.g_over_at_zero[probed], self.g_drain[probed])
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
                if (k, r) in self.split:
                    # generations of one millisecond: the probe met the last,
                    # and no order says which that was
                    lows = set(self.split[(k, r)])
                    if gens[(k, r)][2]:
                        lows.add(0)
                    want = f"limit echoed, remaining one of {sorted(lows)}"
                    want_ok = (got[1] == ks.limit and got[2] in lows)
                elif (k, r) in gens or c_reset == r:
                    taken, at_zero, drained = gens.get((k, r), (0, 0, False))
                    start = c_rem if c_reset == r else ks.limit
                    # the status sticks once a request met an empty bucket;
                    # the refusal that emptied it met what it drained
                    sticky = (at_zero - drained > 0
                              or (c_reset == r and bool(c.sticky_over[k])))
                    want = (OVER_LIMIT if sticky else UNDER_LIMIT, ks.limit,
                            0 if drained else start - taken, r)
                    want_ok = got == want
                else:  # a bucket the probe itself made
                    prev = max(last.get(k, -1), c_reset)
                    if (prev >= 0 and r - ks.duration_ms <= prev
                            and self.early.get(k, 0) >= self.removed[k]):
                        self.evicted.add(k)
                    want = (UNDER_LIMIT, ks.limit, ks.limit, r)
                    want_ok = got == want
            if not want_ok:
                bad += 1
                example = example or f"key={k}: got {got} want {want}"
        v.add("probe.mismatches", bad, 0, example)

    # ---- a guarantee that is eventual ----------------------------------------

    def check_window_eventual(self, it: Items, sent: np.ndarray, born: tuple,
                              join_ms: int, v: Verdict) -> None:
        """The window's items that carry GLOBAL. Between two syncs a copy
        has seen its own hits and not yet the others', so an accepted hit
        on a key the window sent `sent[key]` hits (failed calls included)
        carries start-sent .. start-1, and OVER_LIMIT comes only where
        that many hits use the bucket up. A generation is the carried one
        or began inside `born`, the window's (first, last) millisecond.
        Generations of a key whose reset times lie no more than `join_ms`
        apart are one bucket, made on copies that had not met. By as much a
        bucket may be made anew before the old one's time is up: the step
        that reconciles the copies reads its clock, waits its turn, and
        takes away what has expired by that reading, before a hit that was
        stamped earlier and waited longer is applied."""
        ks, c = self.ks, self.carried
        self.sent = sent
        sel = it.valid & is_global(it.behavior) & ks.is_token(it.key)
        key, rst = it.key[sel], it.reset_time[sel]
        rem, st = it.remaining[sel], it.status[sel]
        order, gid, starts = generations(key, rst)
        key, rst, rem, st = key[order], rst[order], rem[order], st[order]
        g_key, g_reset = key[starts], rst[starts]
        apart = np.ones(len(g_key), dtype=bool)
        apart[1:] = (g_key[1:] != g_key[:-1]) | (g_reset[1:] - g_reset[:-1] > join_ms)
        g_bucket = np.cumsum(apart) - 1
        b_starts = np.nonzero(apart)[0]
        b_key = g_key[b_starts]
        g_carried = c.reset_time[g_key] == g_reset
        b_carried = np.bincount(g_bucket[g_carried], minlength=len(b_key)) > 0
        b_start = np.where(b_carried, c.remaining[b_key], ks.limit)
        acc = st == UNDER_LIMIT
        began = g_reset - ks.duration_ms
        unknown = ~g_carried & ((began < born[0]) | (began > born[1]))
        bucket = g_bucket[gid]
        start = b_start[bucket]
        low = start - sent[key]
        bad_rem = acc & ((rem < low) | (rem > start - 1))
        bad_over = ~acc & (low > 0)

        def item(mask):
            if not mask.any():
                return ""
            i = int(np.argmax(mask))
            return (f"key={int(key[i])} status={int(st[i])} remaining={int(rem[i])} "
                    f"reset_time={int(rst[i])} start={int(start[i])} "
                    f"sent={int(sent[key[i]])}")

        def generation(mask):
            if not mask.any():
                return ""
            i = int(np.argmax(mask))
            return f"key={int(g_key[i])} reset_time={int(g_reset[i])} window={born}"

        v.add("window.global_generation_unknown", np.sum(unknown), 0,
              generation(unknown))
        v.add("window.global_remaining_out_of_range", np.sum(bad_rem), 0,
              item(bad_rem))
        v.add("window.global_over_limit_before_used_up", np.sum(bad_over), 0,
              item(bad_over))
        self.e_key, self.e_reset, self.e_bucket = g_key, g_reset, g_bucket
        self.b_start = b_start
        self.b_accepted = np.bincount(bucket[acc], minlength=len(b_key))
        self.joined = int(len(g_key) - len(b_key))  # generations beside a bucket's first
        self.b_key, self.b_reset = b_key, g_reset[b_starts]
        self.join_ms = join_ms
        self._note_evictions(self.b_key, self.b_reset, b_carried, join_ms)

    def check_probes_eventual(self, repeats: list, v: Verdict) -> None:
        """hits=0 probes sent after the window and after quiescence, every
        key asked once in each of `repeats` (any copy may answer one).
        Every answer for a key that no failed call touched, that showed
        no eviction and that the window could not have used up equals
        (UNDER_LIMIT, limit, start - accepted, reset_time): each
        acknowledged hit counted once, on every copy, and every copy
        holding the same one of a bucket's generations. Where the bucket
        had expired the probe made one, full, on the copy it met."""
        ks, c = self.ks, self.carried
        bucket_of = {(int(k), int(r)): int(b) for k, r, b in
                     zip(self.e_key, self.e_reset, self.e_bucket)}
        last = {}  # a key's newest bucket, by the earliest of its generations
        for k, r in zip(self.b_key.tolist(), self.b_reset.tolist()):
            last[k] = max(last.get(k, -1), r)
        answers: dict = {}  # key -> [(got, want, the bucket asked)]
        for it in repeats:
            for i in np.nonzero(it.valid)[0].tolist():
                k = int(it.key[i])
                got = (int(it.status[i]), int(it.limit[i]),
                       int(it.remaining[i]), int(it.reset_time[i]))
                r = got[3]
                c_reset = int(c.reset_time[k])
                b = bucket_of.get((k, r))
                if b is not None or c_reset == r:
                    start = int(self.b_start[b]) if b is not None else int(c.remaining[k])
                    taken = int(self.b_accepted[b]) if b is not None else 0
                    if int(self.sent[k]) >= start:  # may be used up: not exact
                        continue
                    want = (UNDER_LIMIT, ks.limit, start - taken, r)
                    asked = b if b is not None else "carried"
                else:  # a bucket the probe itself made
                    prev = max(last.get(k, -1), c_reset)
                    if prev >= 0 and r - ks.duration_ms + self.join_ms <= prev:
                        self.evicted.add(k)
                    want = (UNDER_LIMIT, ks.limit, ks.limit, r)
                    asked = None
                answers.setdefault(k, []).append((got, want, asked))
        bad = differ = self.held_exact = 0
        example = example_d = ""
        for k, rows in answers.items():
            if self.uncertain[k] or k in self.evicted:
                continue
            self.held_exact += any(b is not None for _, _, b in rows)
            wrong = [f"got {g} want {w}" for g, w, _ in rows if g != w]
            if wrong:
                bad += 1
                example = example or f"key={k}: {wrong[0]} ({len(wrong)} of {len(rows)} answers)"
            # the copies hold one bucket alike; buckets the probes made on
            # copies that had not met differ in their reset times alone
            seen = {(b, g if b is not None else g[:3]) for g, _, b in rows}
            if len(seen) > len({b for b, _ in seen}):
                differ += 1
                example_d = example_d or f"key={k}: {sorted({g for g, _, _ in rows})}"
        v.add("probe.failed", sum(int(np.sum(~it.valid)) for it in repeats), 0)
        v.add("probe.global_mismatches", bad, 0, example)
        v.add("probe.global_answers_disagree", differ, 0, example_d)

    def check_saved(self, it: Items, saved, hash_keys: list, also_known,
                    v: Verdict) -> None:
        """Stage 4: the snapshot the server saved at shutdown, `saved` =
        (keys, columns) as snapshot.read gives them or the reason why it
        could not be read, against the probes `it` sent just before.
        `hash_keys` are the keyspace's by id; `also_known` the harness's
        own keys (its workers' warm-up calls). A probed key with a sign of
        eviction, or in a call that failed, is excused, as stage 3 excuses
        it; one whose bucket a RESET_REMAINING removed may be missing."""
        ks = self.ks
        unreadable = isinstance(saved, str)
        v.add("save.file_unreadable", int(unreadable), 0, saved if unreadable else "")
        if unreadable:
            for row in ("save.keys_unknown", "save.keys_missing", "save.rows_differ"):
                v.add(row, 0, 0)
            return
        keys, cols = saved
        id_of = {h: k for k, h in enumerate(hash_keys)}
        also_known = set(also_known)
        ids = np.fromiter((id_of.get(h, -1) for h in keys), np.int64, len(keys))
        row_of = np.full(ks.n, -1, np.int64)
        rows = np.nonzero(ids >= 0)[0]
        row_of[ids[rows]] = rows
        # not of the keyspace, or a key that came before
        strange = [keys[i] for i in np.nonzero(ids < 0)[0].tolist()
                   if keys[i] not in also_known]
        twice = len(rows) - int(np.sum(row_of >= 0))
        v.add("save.keys_unknown", len(strange) + twice, 0,
              f"first {strange[:3]}; {twice} rows of a key saved before")
        evicted = np.zeros(ks.n, dtype=bool)
        evicted[sorted(self.evicted)] = True
        held = (it.valid & ks.is_token(it.key) & ~self.uncertain[it.key]
                & ~evicted[it.key])
        row = row_of[it.key]
        missing = held & (row < 0) & (self.removed[it.key] == 0)
        v.add("save.keys_missing", np.sum(missing), 0,
              f"first key ids {it.key[missing][:5].tolist()} of {len(keys)} rows")
        there = held & (row >= 0)
        key, row = it.key[there], row[there]
        differ = ((cols["limit"][row] != it.limit[there])
                  | (cols["duration"][row] != ks.duration_ms)
                  | (cols["remaining"][row] != it.remaining[there])
                  | (cols["expire_at"][row] != it.reset_time[there]))
        example = ""
        if differ.any():
            i = int(np.argmax(differ))
            r = int(row[i])
            example = (f"key={int(key[i])}: saved limit={int(cols['limit'][r])} "
                       f"duration={int(cols['duration'][r])} "
                       f"remaining={int(cols['remaining'][r])} "
                       f"expire_at={int(cols['expire_at'][r])}; probe "
                       f"limit={int(it.limit[there][i])} "
                       f"remaining={int(it.remaining[there][i])} "
                       f"reset_time={int(it.reset_time[there][i])}")
        v.add("save.rows_differ", np.sum(differ), 0, example)
        self.counted.update(saved_rows=len(keys), saved_probed=int(np.sum(there)))

    def check_evictions(self, observed_keys: int, groups: int, ways: int,
                        v: Verdict) -> None:
        v.add("evicted_keys", len(self.evicted),
              eviction_allowance(observed_keys, self.ks.n, groups, ways),
              f"first keys {sorted(self.evicted)[:5]}")
