#!/usr/bin/env python
"""Device time of one program by the phase its operations were traced in.

    JAX_PLATFORMS=cpu python tools/trace_phases.py <trace dir or .xplane.pb> \
        [--program decide_fn] [--phases owner_mask,decide,psum_merge]

`jax.named_scope` puts a phase's name into the metadata of every
operation traced inside it (the `op_name` path, e.g.
``jit(decide_fn)/.../owner_mask/sub``), and the profiler hands that path
back in the metadata of each event of a device plane's `XLA Ops` line. This sums, per
device plane, the time of the operations that ran inside the executions
of `--program` (line `XLA Modules`) by the first of `--phases` their path
holds, and what holds none as "(none)". A fusion carries the path of one
of its operations, so the split is the compiler's, not exact.

The names are metadata only: JAX's compile-cache key strips them, so a
cache that already holds the program compiled from a source without the
scopes serves that executable, and its trace holds no phase. Trace a
process that compiled the program itself (a fresh or disabled cache).

Reads the file as a protobuf (TensorFlow's xplane_pb2) and never touches
a chip.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
NONE = "(none)"


def find_trace(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def phase_of(texts, phases) -> str:
    """The first of `phases` that is a component of a path in `texts`."""
    for text in texts:
        parts = set(re.split(r"[/ ]", text))
        for p in phases:
            if p in parts:
                return p
    return NONE


def read_planes(path: str) -> dict:
    """{device plane: {line: [(start_ns, duration_ns, [texts])]}}. The
    op_name path sits in the stats of an event's METADATA, which
    jax.profiler.ProfileData does not hand back (its `stats` are the
    event's own: offsets and durations), so the file is read as the
    protobuf it is; the message classes ship with TensorFlow."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:") or plane.name.startswith(
                "/device:CUSTOM"):
            continue
        names = {i: m.name for i, m in plane.stat_metadata.items()}

        def strings(stats):
            for st in stats:
                kind = st.WhichOneof("value")
                if kind == "str_value":
                    yield st.str_value
                elif kind == "ref_value":
                    yield names.get(st.ref_value, "")

        texts = {i: [m.name, m.display_name, *strings(m.stats)]
                 for i, m in plane.event_metadata.items()}
        lines = {}
        for line in plane.lines:
            lines[line.name] = [
                (line.timestamp_ns + e.offset_ps // 1000, e.duration_ps // 1000,
                 texts.get(e.metadata_id, []) + list(strings(e.stats)))
                for e in line.events]
        out[plane.name] = lines
    return out


def reduce_plane(lines: dict, program: re.Pattern, phases) -> dict:
    """{phase: seconds}, the executions of `program` and their seconds."""
    runs = sorted((a, a + d) for a, d, texts in lines.get(MODULES_LINE, [])
                  if program.search(texts[0]))
    out = {p: 0.0 for p in (*phases, NONE)}
    i = 0
    for start, dur, texts in sorted(lines.get(OPS_LINE, []), key=lambda o: o[0]):
        while i < len(runs) and runs[i][1] <= start:
            i += 1
        if i < len(runs) and runs[i][0] <= start < runs[i][1]:
            out[phase_of(texts, phases)] += dur * 1e-9
    return {"phases": out, "executions": len(runs),
            "program_s": sum(b - a for a, b in runs) * 1e-9}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--program", default="decide_fn")
    ap.add_argument("--phases", default="owner_mask,decide,psum_merge")
    args = ap.parse_args()
    planes = read_planes(find_trace(args.trace))
    phases = tuple(args.phases.split(","))
    program = re.compile(args.program)
    rows = {name: reduce_plane(lines, program, phases)
            for name, lines in planes.items()}
    for name, row in sorted(rows.items()):
        n = max(row["executions"], 1)
        split = "  ".join(f"{p} {1e6 * s / n:.1f}" for p, s in row["phases"].items())
        print(f"{name}: {row['executions']} executions of /{args.program}/, "
              f"{1e6 * row['program_s'] / n:.1f} us each; us an execution by phase: {split}")
    print(json.dumps(rows))
    return 0 if rows else 1


if __name__ == "__main__":
    sys.exit(main())
