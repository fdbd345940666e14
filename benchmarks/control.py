"""The control: the served path with one stated guarantee broken, which
the comparison has to call not correct.

    python benchmarks/control.py <kind> <listen port> <server address>

A gRPC relay of the benchmark's own that stands between the load
generators and the server during a ``--control <kind>`` run. It passes
``V1/GetRateLimits`` through as bytes and breaks one guarantee on every
20th call:

    double_apply   the call is applied twice and the second answer
                   returned: an acknowledged hit is counted twice
    stale_answer   the call is applied, but the caller gets the answer of
                   the previous call of the same size: the answers no
                   longer follow the order the hits were applied in
    forget         the call is answered from buckets made anew (its keys
                   reach the server under another name): live buckets are
                   forgotten outside any over-full group
    strip_flags    the call reaches the server with RESET_REMAINING and
                   DRAIN_OVER_LIMIT cleared from every item: a bucket that
                   was to be removed or emptied is not

The benchmark's own runs never start it. The parent's own calls
(preload, set-up check, probes) go to the server directly, so the fault
sits under the timed path alone.

Two more kinds need no relay: they break what a Loader-attached daemon is
handed at start or hands back at shutdown (run.py calls them on the
snapshot files, snapshot.py):

    stale_snapshot the checkpoint the server loads holds ``remaining`` one
                   too high for one key in 1,000: the Load gives back a
                   hit that was acknowledged
    drop_saved     one row in 1,000 of the checkpoint the server saved is
                   gone before the check reads it: a Save that lacks keys
"""

from __future__ import annotations

import asyncio
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import wire  # noqa: E402

EVERY = 20
KINDS = ("double_apply", "stale_answer", "forget", "strip_flags")
STRIPPED = wire.BEHAVIOR["RESET_REMAINING"] | wire.BEHAVIOR["DRAIN_OVER_LIMIT"]
SNAPSHOT_KINDS = ("stale_snapshot", "drop_saved")
ONE_IN = 1000


def stale_snapshot(cols: dict) -> None:
    """In place: every `ONE_IN`-th row of a snapshot's columns gets back one
    hit it had taken."""
    cols["remaining"][::ONE_IN] += 1


def drop_saved(keys: list, cols: dict) -> tuple:
    """The snapshot without every `ONE_IN`-th row."""
    import numpy as np

    keep = np.arange(len(keys)) % ONE_IN != 0
    return ([k for k, kept in zip(keys, keep.tolist()) if kept],
            {c: v[keep] for c, v in cols.items()})


async def main_async(kind: str, port: int, target: str) -> None:
    import grpc

    options = wire.CHANNEL_OPTIONS
    channel = grpc.aio.insecure_channel(target, options=options)
    upstream = channel.unary_unary(wire.METHOD, request_serializer=None,
                                   response_deserializer=None)
    state = {"n": 0, "last": {}}

    async def relay(request: bytes, context) -> bytes:
        state["n"] += 1
        broken = state["n"] % EVERY == 0
        if broken and kind in ("forget", "strip_flags"):
            msg = wire.GetReq.FromString(request)
            for r in msg.requests:
                if kind == "forget":
                    r.unique_key += "~forgotten"
                else:
                    r.behavior &= ~STRIPPED
            request = msg.SerializeToString()
        answer = await upstream(request, timeout=30)
        if broken and kind == "double_apply":
            answer = await upstream(request, timeout=30)
        if kind == "stale_answer":
            previous = state["last"].get(len(answer))
            state["last"][len(answer)] = answer
            if broken and previous is not None:
                answer = previous
        return answer

    service, method = wire.METHOD.strip("/").split("/")
    server = grpc.aio.server(options=options)
    server.add_generic_rpc_handlers([grpc.method_handlers_generic_handler(
        service, {method: grpc.unary_unary_rpc_method_handler(
            relay, request_deserializer=None, response_serializer=None)})])
    server.add_insecure_port(f"127.0.0.1:{port}")
    await server.start()
    print("READY", flush=True)
    await server.wait_for_termination()


def main() -> int:
    kind, port, target = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    if kind not in KINDS:
        print(f"unknown control {kind!r}: one of {KINDS}", flush=True)
        return 2
    asyncio.run(main_async(kind, port, target))
    return 0


if __name__ == "__main__":
    sys.exit(main())
