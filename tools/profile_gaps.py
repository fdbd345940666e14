#!/usr/bin/env python
"""Name the device's idle time in a /debug/profile capture.

    JAX_PLATFORMS=cpu python tools/profile_gaps.py <trace dir or .xplane.pb>

A capture holds the device planes and, in plane /host:CPU on the same
clock, the program's own spans (docs/monitoring.md "Tracing the
pipeline"): `rpc.begin` / `rpc.end` marks, `call.*` and `flush.*`, each
carrying the `call` and `flush` ids, and what no call owns: `tick.*`,
`complete.idle`, `loop.lag`, `interp.wait`. Seventeen threads have
spans open at once, so "which host span covers the gap" has no single
answer. The rule here:

- a gap on a device plane ends when a device program starts;
- that program was launched by the flush whose `flush.dispatch` span
  last began before it;
- the gap is divided along that flush's own call: the parts of the gap
  its call spent in `executor_wait` (from `rpc.begin` to the call's
  first span on another thread), `parse`, `route`, `queue` (the pump's
  queue: the `flush.queue` mark, which carries its length), `join` (the
  leader of a merged columnar flush waiting for its turn: the flush
  before it had not launched yet), `hash`, `waves`, `keydict`,
  `lock_wait` and `dispatch`, and, before `rpc.begin`, "call not yet in
  the server"; with a Store the two stages inside `dispatch` come first:
  `readthrough` (the probe's launch and read, `Store.get`, the inject)
  and `store_rows` (the row gather's launch and the wave's two reads);
- what none of these covers goes to another flush's `readback` or
  `post` where one was open (a pipelined pump waits for the flush before
  last to be read before it launches the next);
- then to what no call owns, whichever gap it is: the sync tick's
  `tick.launch`, `tick.read` and `tick.lock_wait` (it holds, or waits
  for, the engine lock the flushes need), `interp.wait` and `loop.lag`
  (marks at the end of a wait for the interpreter lock or of the
  serving loop's lateness, each as long as it says), `complete.idle`
  (the completion thread had no ticket: nothing was in flight);
- and is "unattributed" otherwise (the runtime's own queue after the
  launch, a wait no span times, a gap that ends with the capture).

Prints seconds per name and per device plane; the names add up to the
plane's idle time. For the device programs of a Store's per-wave
sequence it also prints, per plane, their executions and time, and the
`jax.named_scope` phases inside each (STORE_PHASES: on a mesh
`owner_mask`, `probe_local`, `psum_probe`; `owner_mask`, `decide`,
`global_slot`, `psum_merge`; `owner_mask`, `store_rows_local`,
`psum_rows`), which `tools/trace_phases.py --program <name> --phases
<list>` splits a capture by. Reads the file with jax.profiler.ProfileData on the
CPU backend and never touches a chip.
"""

from __future__ import annotations

import bisect
import glob
import os
import sys

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
NOT_YET = "call not yet in the server"
UNATTRIBUTED = "unattributed"
OTHER = {"flush.readback": "another flush: readback",
         "flush.post": "another flush: post"}
# Innermost first: where two of a call's intervals overlap (a pump
# flush beside its call's own thread) the gap goes to the earlier name.
STAGES = ("readthrough", "store_rows", "dispatch", "lock_wait", "keydict",
          "waves", "hash", "join", "queue", "route", "parse", "executor_wait")
# What no call owns, under its span's own name, in the order a gap
# goes to them once the launching flush's call and the other flushes
# have had theirs.
GLOBAL = ("tick.launch", "tick.read", "tick.lock_wait", "interp.wait",
          "loop.lag", "complete.idle")
# A mark at the end of a wait carries the wait's length under this key.
WAIT_US = {"flush.queue": "wait_us", "interp.wait": "wait_us",
           "loop.lag": "lag_us"}
PREFIXES = ("rpc.", "call.", "flush.", "tick.", "complete.", "loop.",
            "interp.")
RANK = {name: i for i, name in enumerate(
    STAGES + (NOT_YET,) + tuple(OTHER.values()) + GLOBAL)}
ORDER = (NOT_YET, "executor_wait", "parse", "route", "queue", "join",
         "hash", "waves", "keydict", "lock_wait", "dispatch", "readthrough",
         "store_rows", *OTHER.values(), *GLOBAL, UNATTRIBUTED)
# The device programs of a Store's per-wave sequence, by a part of their
# name in a capture (one chip: jit_probe_exists_fused, jit_decide_fused,
# jit_gather_rows_fused; a mesh: jit_probe_exists_fn, jit_decide_fn,
# jit_gather_rows_fn), and the named phases inside each on a mesh
# (parallel/mesh.py).
STORE_PHASES = {
    "probe_exists": ("owner_mask", "probe_local", "psum_probe"),
    "decide": ("owner_mask", "decide", "global_slot", "psum_merge"),
    "gather_rows": ("owner_mask", "store_rows_local", "psum_rows"),
    "inject": (),
}


def find_trace(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def busy_gaps(programs, t_lo: float, t_hi: float) -> list:
    """[(gap start, gap end, a program starts at its end)] between the
    busy intervals of `programs` = [(start, end)], window edges included."""
    out = []
    end = t_lo
    for a, b in sorted(programs):
        if a > end:
            out.append((end, a, True))
        end = max(end, b)
    if t_hi > end:
        out.append((end, t_hi, False))
    return out


def call_intervals(call_spans: list, flush_spans: list) -> list:
    """The named intervals of one call for one of its flushes:
    [(start, end, name)]. `call_spans` are the call's own spans,
    `flush_spans` those of the flush; both [(start, end, span name)]."""
    out = []
    begin = min((a for a, _, n in call_spans if n == "rpc.begin"), default=None)
    if begin is not None:
        out.append((float("-inf"), begin, NOT_YET))
        first = min((a for a, _, n in call_spans
                     if a >= begin and n.startswith("call.")), default=None)
        if first is not None:
            out.append((begin, first, "executor_wait"))
    for a, b, n in call_spans + flush_spans:
        label = n.rpartition(".")[2]
        if label in STAGES and n.split(".")[0] in ("call", "flush"):
            out.append((a, b, label))
    return out


def divide(gap: tuple, intervals: list) -> dict:
    """Seconds of `gap` = (start, end) per name of `intervals`; each
    instant goes to the first of STAGES (then NOT_YET, then OTHER, then
    GLOBAL) that covers it."""
    g0, g1 = gap
    cuts = sorted({g0, g1, *(t for a, b, _ in intervals for t in (a, b)
                             if g0 < t < g1)})
    out: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        names = [n for s, e, n in intervals if s <= mid < e]
        name = min(names, key=RANK.__getitem__) if names else UNATTRIBUTED
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def attribute_plane(programs: list, spans: list, t_lo: float, t_hi: float) -> dict:
    """`programs`: [(start, end)] of one device plane. `spans`:
    [(start, end, name, call id, flush id)] of the host plane. Returns
    {name: idle seconds}; the values add up to the plane's idle time."""
    dispatches = sorted((a, fl) for a, _, n, _, fl in spans
                        if n == "flush.dispatch")
    starts = [a for a, _ in dispatches]
    by_flush: dict = {}
    by_call: dict = {}
    flush_call: dict = {}
    for a, b, n, call, fl in spans:
        if n.startswith("flush."):
            by_flush.setdefault(fl, []).append((a, b, n))
            flush_call[fl] = call
        else:
            by_call.setdefault(call, []).append((a, b, n))
    owned_by_none = [(a, b, n) for a, b, n, _, _ in spans if n in GLOBAL]
    totals: dict = {}
    for g0, g1, launched in busy_gaps(programs, t_lo, t_hi):
        intervals = [(a, b, n) for a, b, n in owned_by_none
                     if a < g1 and b > g0]
        i = bisect.bisect_right(starts, g1) - 1
        if launched and i >= 0:
            fl = dispatches[i][1]
            intervals += [(a, b, OTHER[n]) for a, b, n, _, f in spans
                          if n in OTHER and f != fl and a < g1 and b > g0]
            intervals += call_intervals(
                by_call.get(flush_call.get(fl), []), by_flush.get(fl, []))
        parts = divide((g0, g1), intervals)
        for name, secs in parts.items():
            totals[name] = totals.get(name, 0.0) + secs
    return totals


def store_programs(named: list) -> dict:
    """{program of STORE_PHASES: (executions, seconds)} over one plane's
    [(program name, seconds)]."""
    out: dict = {}
    for name, secs in named:
        for part in STORE_PHASES:
            if part in name:
                n, s = out.get(part, (0, 0.0))
                out[part] = (n + 1, s + secs)
                break
    return out


def read_trace(path: str, names: dict = None) -> tuple:
    """({device plane: [(start, end)] of its programs}, host spans);
    `names`, if given, is filled with {device plane: [(program name,
    seconds)]}."""
    from jax.profiler import ProfileData

    planes: dict = {}
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and not plane.name.startswith(
                "/device:CUSTOM"):
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get(MODULES_LINE) or lines.get(OPS_LINE)
            if line is not None:
                planes[plane.name] = [
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.duration_ns > 0]
                if names is not None and line.name == MODULES_LINE:
                    names[plane.name] = [(e.name, e.duration_ns * 1e-9)
                                         for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        st = dict(e.stats)
                        a = e.start_ns * 1e-9
                        b = a + e.duration_ns * 1e-9
                        if e.name in WAIT_US:  # a mark at the wait's end
                            a = b - int(st.get(WAIT_US[e.name], 0)) * 1e-6
                        spans.append((a, b, e.name,
                                      int(st.get("call", 0)),
                                      int(st.get("flush", 0))))
    return planes, spans


def main(argv: list) -> int:
    if len(argv) != 2:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    programs: dict = {}
    planes, spans = read_trace(find_trace(argv[1]), programs)
    names = sorted({n for _, _, n, _, _ in spans})
    print(f"host spans: {len(spans)} of {len(names)} names: {' '.join(names)}")
    if not planes:
        print("no device plane in this capture (a CPU backend has none)")
        return 0
    every = [t for evs in planes.values() for ab in evs for t in ab]
    t_lo, t_hi = min(every), max(every)
    for name in sorted(planes):
        totals = attribute_plane(planes[name], spans, t_lo, t_hi)
        idle = sum(totals.values())
        print(f"{name}: window {t_hi - t_lo:.6f} s, idle {idle:.6f} s "
              f"({100 * idle / (t_hi - t_lo):.2f} %), "
              f"programs {len(planes[name])}")
        for stage in ORDER:
            if stage in totals:
                print(f"  {stage:<28} {totals[stage]:>10.6f} s "
                      f"{100 * totals[stage] / idle:>6.2f} % of idle")
        for part, (n, secs) in sorted(
                store_programs(programs.get(name, [])).items()):
            print(f"  program {part:<20} {n:>6} x {1e6 * secs / n:>8.1f} us"
                  f"  phases: {' '.join(STORE_PHASES[part]) or '-'}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
