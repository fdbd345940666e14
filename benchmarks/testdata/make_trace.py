#!/usr/bin/env python3
"""Writes ``synthetic.xplane.pb``: a profiler trace with a schedule known
by hand, for the tests of trace_reduce.py.

XSpace/XPlane/XLine/XEvent are encoded here field by field (tsl's
xplane.proto: planes=1; id=1 name=2 lines=3 event_metadata=4; line id=1
name=2 timestamp_ns=3 events=4; event metadata_id=1 offset_ps=2
duration_ps=3; metadata id=1 name=2).

Microseconds from the trace's origin, two chips:

  /device:TPU:0  XLA Modules  jit_decide_fused(123)  1000-1300, 3000-3300, 6000-6300
                              jit_census(7)          4000-4500
                 XLA Ops      each decide: %copy.1 100 us, then %fusion.2 (X64Combine) 200 us
                              census: %reduce.9 500 us
  /device:TPU:1  XLA Modules  jit_decide_fused(123)  2000-3000   (XLA Ops: %copy.1 1000 us)
  /host:CPU      python       stop_trace             0-9000      (outlasts the device planes)

So the traced window is 1000-6300 = 5300 us; chip 0 is busy 1400 us, chip 1
1000 us, 1200 us on average; decide ran 4 times for 1900 us (950 us a chip);
chip 0's gaps: decide->decide 1700, decide->census 700, census->decide 1500.
"""

import os


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def plane(pid: int, name: str, lines: dict) -> bytes:
    names = sorted({n for evs in lines.values() for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    body = field(1, pid) + field(2, name)
    for lid, (lname, evs) in enumerate(lines.items(), start=1):
        line = field(1, lid) + field(2, lname) + field(3, 0)
        for n, start_us, dur_us in evs:
            line += field(4, field(1, ids[n]) + field(2, start_us * 1_000_000)
                          + field(3, dur_us * 1_000_000))
        body += field(3, line)
    for n, i in ids.items():
        body += field(4, field(1, i) + field(2, field(1, i) + field(2, n)))
    return body


DECIDE = "jit_decide_fused(123)"
COPY = "%copy.1 = s64[65536,10]{1,0} copy(s64[65536,10]{0,1} %table_data.1)"
FUSION = ('%fusion.2 = s64[65536,10]{1,0} custom-call(u32[65536,10] %a), '
          'custom_call_target="X64Combine"')
REDUCE = "%reduce.9 = s32[64]{0} reduce(s32[65536]{0} %x)"

chip0_modules = [(DECIDE, 1000, 300), (DECIDE, 3000, 300), ("jit_census(7)", 4000, 500),
                 (DECIDE, 6000, 300)]
chip0_ops = []
for name, start, dur in chip0_modules:
    if name == DECIDE:
        chip0_ops += [(COPY, start, 100), (FUSION, start + 100, 200)]
    else:
        chip0_ops += [(REDUCE, start, dur)]

space = b"".join(field(1, p) for p in (
    plane(1, "/device:TPU:0", {"XLA Modules": chip0_modules, "XLA Ops": chip0_ops}),
    plane(2, "/device:TPU:1", {"XLA Modules": [(DECIDE, 2000, 1000)],
                               "XLA Ops": [(COPY, 2000, 1000)]}),
    plane(3, "/host:CPU", {"python": [("stop_trace", 0, 9000)]}),
))

if __name__ == "__main__":
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "synthetic.xplane.pb")
    with open(out, "wb") as f:
        f.write(space)
    print(out, len(space))
