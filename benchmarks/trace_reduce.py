"""From a profiler trace (``.xplane.pb``) to numbers.

    JAX_PLATFORMS=cpu python benchmarks/trace_reduce.py <trace dir or file> <out.json>

Run as a helper process pinned to the CPU backend: it reads the file with
``jax.profiler.ProfileData`` and never touches a chip.

For each device plane (``/device:TPU:n``): the traced window, the busy
time (union of the intervals in which an operation ran), the time of
each program (line ``XLA Modules``: one event per execution of a jitted
program, named ``jit_<function>(...)``) and of each operation (line ``XLA
Ops``), and the idle gaps. The program sets no host spans yet, so a gap
is labelled by the programs on either side of it. ``breakdown`` holds the
operations that took most device time and the longest kinds of gap.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def find_trace(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def op_label(event_name: str) -> str:
    """An HLO instruction's text, cut to its name and, for a custom call,
    the target: ``%custom-call.1 X64SplitLow``."""
    head = event_name.split(" = ", 1)[0][:80]
    target = re.search(r'custom_call_target="([^"]+)"', event_name)
    return f"{head} {target.group(1)}" if target else head


def program_name(event_name: str) -> str:
    """``jit_decide_fused(1234567890)`` -> ``jit_decide_fused``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def union_seconds(intervals) -> float:
    """Length of the union of (start, end) pairs."""
    busy = 0.0
    end = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    return busy


def gaps_of(events, t_lo: float, t_hi: float) -> list:
    """[(seconds, name before, name after)] between consecutive busy
    intervals of `events` = [(start, end, name)], window edges included."""
    out = []
    end, last = t_lo, "window opens"
    for a, b, name in sorted(events):
        if a > end:
            out.append((a - end, last, name))
        if b > end:
            end, last = b, name
    if t_hi > end:
        out.append((t_hi - end, last, "window closes"))
    return out


def reduce_plane(lines: dict, t_lo: float, t_hi: float) -> dict:
    """`lines`: {line name: [(start_s, end_s, name)]} of one device plane."""
    ops = lines.get(OPS_LINE)
    modules = lines.get(MODULES_LINE)
    if ops is None:  # a backend without the two lines: every timed event
        ops = [e for evs in lines.values() for e in evs]
    if modules is None:
        modules = ops
    busy = union_seconds([(a, b) for a, b, _ in ops])
    programs: dict = {}
    for a, b, name in modules:
        row = programs.setdefault(program_name(name), [0, 0.0])
        row[0] += 1
        row[1] += b - a
    op_sums: dict = {}
    for a, b, name in ops:
        label = op_label(name)
        op_sums[label] = op_sums.get(label, 0.0) + (b - a)
    gap_sums: dict = {}
    for secs, before, after in gaps_of(modules, t_lo, t_hi):
        label = f"{program_name(before)} -> {program_name(after)}"
        gap_sums[label] = gap_sums.get(label, 0.0) + secs
    return {
        "busy_s": busy,
        "programs": programs,
        "ops": sorted(op_sums.items(), key=lambda kv: -kv[1])[:TOP],
        "gaps": sorted(gap_sums.items(), key=lambda kv: -kv[1])[:TOP],
    }


def reduce_planes(planes: dict) -> dict:
    """`planes`: {plane name: {line name: [(start_s, end_s, name)]}}."""
    device_names = sorted(n for n in planes if n.startswith("/device:")
                          and not n.startswith("/device:CUSTOM"))
    # The traced window is what the device planes span: host events go on
    # for seconds after the device stops recording (the profiler's own stop).
    every = [e for n in (device_names or planes) for evs in planes[n].values()
             for e in evs]
    t_lo = min((a for a, _, _ in every), default=0.0)
    t_hi = max((b for _, b, _ in every), default=0.0)
    devices = [dict(reduce_plane(planes[n], t_lo, t_hi), name=n)
               for n in device_names]
    window = t_hi - t_lo
    out = {
        "plane_names": sorted(planes),
        "line_names": {n: sorted(planes[n]) for n in device_names},
        "window_s": window,
        "devices": devices,
        "busy_s": None, "idle_share_pct": None, "breakdown": None,
    }
    if devices:
        busy = sum(d["busy_s"] for d in devices) / len(devices)
        out["busy_s"] = busy
        out["idle_share_pct"] = 100.0 * (1.0 - busy / window) if window else None
        prog: dict = {}
        gaps: dict = {}
        for d in devices:
            for name, secs in d["ops"]:
                prog[name] = prog.get(name, 0.0) + secs / len(devices)
            for label, secs in d["gaps"]:
                gaps[label] = gaps.get(label, 0.0) + secs / len(devices)
        out["breakdown"] = {
            "device_ops": sorted(([n, s] for n, s in prog.items()),
                                 key=lambda r: -r[1])[:TOP],
            "idle_gaps": sorted(([n, s] for n, s in gaps.items()),
                                key=lambda r: -r[1])[:TOP],
        }
    return out


def read_planes(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: dict = {}
    for plane in data.planes:
        lines: dict = {}
        for line in plane.lines:
            evs = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                   for e in line.events if e.duration_ns > 0]
            if evs:
                lines.setdefault(line.name, []).extend(evs)
        if lines:
            planes[plane.name] = lines
    return planes


def main() -> int:
    src, out = sys.argv[1], sys.argv[2]
    result = reduce_planes(read_planes(find_trace(src)))
    with open(out, "w", encoding="utf-8") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
