"""Lock-order sanitizer: deadlock detection for the threaded hot paths.

The serving tier holds several threading locks concurrently (engine
table swap vs key-dictionary, metrics registry vs engine flush,
telemetry install); a deadlock needs two threads acquiring the same
pair in opposite orders — which no single-threaded test ever trips.
This module makes the ORDER itself the tested invariant:

- ``make_lock(name)`` / ``make_rlock(name)`` are drop-in factories the
  production modules use instead of ``threading.Lock()`` /
  ``threading.RLock()``. With ``GUBER_LOCK_SANITIZER`` unset they
  return the raw ``threading`` primitive — zero wrapper overhead in
  production.
- Under ``GUBER_LOCK_SANITIZER=1`` (the tier-1 test session sets this
  in conftest.py) they return a wrapper that tracks each thread's
  held-lock set and accumulates a global acquisition-order graph
  (edge A->B = "B was acquired while A was held", with the witness
  stack). Two violation kinds are recorded at *attempt* time, before
  the acquire can block:

  * ``cycle`` — acquiring B while holding A when the graph already
    contains a path B ->* A: the classic AB/BA inversion, even if the
    two orders happened on the same thread at different times and
    never actually deadlocked in this run;
  * ``double-acquire`` — re-acquiring a non-reentrant Lock the thread
    already holds (guaranteed self-deadlock).

Violations accumulate on the graph (default: the module-global
``DEFAULT_GRAPH``); the test session asserts the default graph stays
empty after every test, so the existing engine/peer/gateway
concurrency tests double as race-order probes. Deliberate-violation
tests construct their own ``LockOrderGraph`` so they never pollute the
session-wide report.

Ordering is keyed by lock NAME, not instance: every per-engine
``engine.table`` lock is one graph node, so an inversion between two
different engine instances' locks is still reported. Name locks by
role, not by object identity.
"""

from __future__ import annotations

import os
import threading
import traceback
from typing import Dict, List, Optional, Tuple


def enabled() -> bool:
    """Sanitizer gate, read at lock-construction time (not import time,
    so config-file env injection and test sessions can flip it)."""
    return os.environ.get("GUBER_LOCK_SANITIZER", "") in ("1", "true")


def _site(skip: int = 3) -> str:
    """Compact acquisition-site witness: 'file:line in func'."""
    for frame in reversed(traceback.extract_stack()[:-skip]):
        if "lockorder" not in frame.filename:
            return f"{frame.filename}:{frame.lineno} in {frame.name}"
    return "<unknown>"


class LockOrderGraph:
    """Global acquisition-order graph + per-thread held stacks."""

    def __init__(self) -> None:
        # A plain lock: the graph itself must not route through the
        # sanitizer it implements.
        self._mu = threading.Lock()
        # edges[a][b] = first witness site of acquiring b while holding a
        self.edges: Dict[str, Dict[str, str]] = {}
        self.violations: List[dict] = []
        self._local = threading.local()

    # -- per-thread held stack -------------------------------------------

    def _held(self) -> List[Tuple[str, int]]:
        """This thread's held stack as (name, lock-id) in acquire order."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = []
            self._local.stack = stack
        return stack

    # -- graph ------------------------------------------------------------

    def _path_exists(self, src: str, dst: str) -> Optional[List[str]]:
        """DFS path src ->* dst over recorded edges (caller holds _mu)."""
        seen = {src}
        todo = [(src, [src])]
        while todo:
            node, path = todo.pop()
            if node == dst:
                return path
            for nxt in self.edges.get(node, ()):
                if nxt not in seen:
                    seen.add(nxt)
                    todo.append((nxt, path + [nxt]))
        return None

    def note_attempt(self, name: str, lock_id: int, reentrant: bool) -> None:
        """Called BEFORE the underlying acquire so a would-deadlock
        attempt is reported even if the acquire then blocks forever
        (or times out in a test)."""
        held = self._held()
        if not reentrant and any(lid == lock_id for _, lid in held):
            with self._mu:
                self.violations.append({
                    "kind": "double-acquire",
                    "lock": name,
                    "thread": threading.current_thread().name,
                    "site": _site(),
                })
            return
        if reentrant and any(lid == lock_id for _, lid in held):
            return  # RLock re-entry establishes no new ordering
        held_names = []
        for prior, _ in held:
            if prior != name and prior not in held_names:
                held_names.append(prior)
        if not held_names:
            return
        with self._mu:
            # The witness site is a stack walk (traceback reads source
            # lines): taken only for an ordering not seen before, so an
            # acquire on a hot path costs a dict probe.
            held_names = [
                p for p in held_names if name not in self.edges.get(p, ())
            ]
            if not held_names:
                return
            site = _site()
            for prior in held_names:
                # Inversion check BEFORE inserting prior->name: a path
                # name ->* prior means some execution acquired these in
                # the opposite order.
                path = self._path_exists(name, prior)
                if path is not None:
                    key = (prior, name)
                    already = any(
                        v["kind"] == "cycle" and v["edge"] == key
                        for v in self.violations
                    )
                    if not already:
                        self.violations.append({
                            "kind": "cycle",
                            "edge": key,
                            "cycle": path + [name],
                            "thread": threading.current_thread().name,
                            "site": site,
                            "witnesses": {
                                f"{a}->{b}": self.edges[a][b]
                                for a, b in zip(path, path[1:])
                                if a in self.edges and b in self.edges[a]
                            },
                        })
                self.edges.setdefault(prior, {}).setdefault(name, site)

    def note_acquired(self, name: str, lock_id: int) -> None:
        self._held().append((name, lock_id))

    def note_release(self, name: str, lock_id: int) -> None:
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i] == (name, lock_id):
                del held[i]
                return

    # -- reporting ---------------------------------------------------------

    def report(self) -> List[dict]:
        with self._mu:
            return list(self.violations)

    def format_report(self) -> str:
        lines = []
        for v in self.report():
            if v["kind"] == "double-acquire":
                lines.append(
                    f"double-acquire of non-reentrant lock '{v['lock']}' "
                    f"on thread {v['thread']} at {v['site']}"
                )
            else:
                cyc = " -> ".join(v["cycle"])
                lines.append(
                    f"lock-order inversion {cyc} (edge "
                    f"{v['edge'][0]}->{v['edge'][1]} at {v['site']}; "
                    f"prior witnesses: {v['witnesses']})"
                )
        return "\n".join(lines)

    def clear(self) -> None:
        with self._mu:
            self.edges.clear()
            self.violations.clear()


DEFAULT_GRAPH = LockOrderGraph()


class SanitizedLock:
    """Order-tracking wrapper over threading.Lock/RLock. API-compatible
    for acquire/release/locked/context-manager use."""

    __slots__ = ("_name", "_lock", "_graph", "_reentrant")

    def __init__(self, name, lock, graph, reentrant):
        self._name = name
        self._lock = lock
        self._graph = graph
        self._reentrant = reentrant

    @property
    def name(self) -> str:
        return self._name

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        self._graph.note_attempt(self._name, id(self), self._reentrant)
        ok = self._lock.acquire(blocking, timeout)
        if ok:
            self._graph.note_acquired(self._name, id(self))
        return ok

    def release(self) -> None:
        self._lock.release()
        self._graph.note_release(self._name, id(self))

    def locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __repr__(self):
        kind = "RLock" if self._reentrant else "Lock"
        return f"<SanitizedLock {kind} {self._name!r} wrapping {self._lock!r}>"


def make_lock(name: str, graph: Optional[LockOrderGraph] = None):
    """threading.Lock() drop-in; sanitized only under GUBER_LOCK_SANITIZER."""
    if not enabled():
        return threading.Lock()
    return SanitizedLock(name, threading.Lock(), graph or DEFAULT_GRAPH, False)


def make_rlock(name: str, graph: Optional[LockOrderGraph] = None):
    """threading.RLock() drop-in; sanitized only under GUBER_LOCK_SANITIZER."""
    if not enabled():
        return threading.RLock()
    return SanitizedLock(name, threading.RLock(), graph or DEFAULT_GRAPH, True)
