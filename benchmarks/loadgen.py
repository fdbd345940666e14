"""One load-generator worker: a JAX-free process of the benchmark's own
that speaks gRPC ``V1/GetRateLimits`` through ``wire.py``.

    python benchmarks/loadgen.py <job.pkl> <out.npz>

The job (written by run.py) holds the worker's share of the plan: encoded
calls, and either the callers whose pools they are (closed loop) or the
seconds at which each is due (open loop). The worker connects, prints
``READY``, reads ``GO <monotonic start> <seconds>`` from stdin
(CLOCK_MONOTONIC is one clock for every process of the machine), runs
the window, waits for what is still in flight, decodes every response
and writes them out. One thread: an asyncio loop over ``grpc.aio``.

Open loop: a call is sent when it is due, never earlier, whatever the
replies do; it is timed from when it was due. Closed loop: a caller
sends its next call when the reply arrives; a call is timed from send.
"""

from __future__ import annotations

import asyncio
import os
import pickle
import sys
import time

import grpc
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import wire  # noqa: E402

SPIN_S = 0.002  # sleep coarsely until this close to a due time, then yield-spin


class Recorder:
    """Per-call rows, appended as calls finish (times relative to the
    window's opening)."""

    def __init__(self):
        self.call = []  # plan call index
        self.due = []
        self.sent = []
        self.done = []
        self.ok = []  # the RPC itself returned
        self.raw = []  # response bytes, or the gRPC status code's name

    def add(self, call, due, sent, done, raw):
        self.call.append(call)
        self.due.append(due)
        self.sent.append(sent)
        self.done.append(done)
        self.ok.append(isinstance(raw, bytes))
        self.raw.append(raw)


async def one_call(stub, blob, deadline_s):
    try:
        return await stub(blob, timeout=deadline_s)
    except grpc.aio.AioRpcError as e:
        return e.code().name


async def closed_caller(stub, pool, blobs, t0, t_end, deadline_s, rec):
    """`pool`: plan call indices of this caller, cycled."""
    i = 0
    while True:
        sent = time.monotonic()
        if sent >= t_end:
            return
        idx = pool[i % len(pool)]
        raw = await one_call(stub, blobs[idx], deadline_s)
        rec.add(idx, sent - t0, sent - t0, time.monotonic() - t0, raw)
        i += 1


async def open_dispatch(stubs, calls, due, blobs, t0, deadline_s, rec):
    pending = []

    async def fire(idx, t_due, stub):
        sent = time.monotonic()
        raw = await one_call(stub, blobs[idx], deadline_s)
        rec.add(idx, t_due - t0, sent - t0, time.monotonic() - t0, raw)

    for n, (idx, d) in enumerate(zip(calls, due)):
        t_due = t0 + d
        while True:
            left = t_due - time.monotonic()
            if left <= 0:
                break
            # coarse sleep, then hand the loop its turn until the time comes
            await asyncio.sleep(left - SPIN_S if left > SPIN_S else 0)
        pending.append(asyncio.ensure_future(
            fire(idx, t_due, stubs[n % len(stubs)])))
    if pending:
        await asyncio.wait(pending)


async def run(job):
    channels = [grpc.aio.insecure_channel(t, options=wire.CHANNEL_OPTIONS)
                for t in job["targets"]]
    stubs = [
        ch.unary_unary(wire.METHOD, request_serializer=None,
                       response_deserializer=None)
        for ch in channels
    ]
    for ch in channels:
        await asyncio.wait_for(ch.channel_ready(), 60)
    for stub in stubs:
        # the connection's first call is not a timed one: a hits=0 look at a
        # key outside the configuration's keyspace
        await stub(job["warmup"], timeout=30)
    print("READY", flush=True)
    line = await asyncio.get_running_loop().run_in_executor(
        None, sys.stdin.readline)
    word, t0, seconds = line.split()
    if word != "GO":
        raise SystemExit(2)
    t0, seconds = float(t0), float(seconds)
    rec = Recorder()
    blobs = job["blobs"]
    deadline_s = float(job["deadline_s"])
    await asyncio.sleep(max(t0 - time.monotonic(), 0))
    if job["loop"] == "closed":
        await asyncio.gather(*(
            closed_caller(stubs[c % len(stubs)], pool, blobs, t0,
                          t0 + seconds, deadline_s, rec)
            for c, pool in job["pools"].items()
        ))
    else:
        await open_dispatch(stubs, job["calls"], job["due"], blobs, t0,
                            deadline_s, rec)
    for ch in channels:
        await ch.close()
    return rec


def write_out(rec: Recorder, path: str) -> None:
    """Decode every response and save flat arrays: per call, and per item
    with `offsets` marking each call's slice."""
    n_items = []
    cols = [[], [], [], [], []]  # status, limit, remaining, reset_time, has error
    first_error = ""
    for raw in rec.raw:
        rows = wire.decode_call(raw) if isinstance(raw, bytes) else []
        n_items.append(len(rows))
        for st, lim, rem, rst, err in rows:
            cols[0].append(st)
            cols[1].append(lim)
            cols[2].append(rem)
            cols[3].append(rst)
            cols[4].append(1 if err else 0)
            if err and not first_error:
                first_error = err
    offsets = np.concatenate([[0], np.cumsum(n_items)]).astype(np.int64)
    np.savez(
        path,
        call=np.asarray(rec.call, dtype=np.int64),
        due=np.asarray(rec.due, dtype=np.float64),
        sent=np.asarray(rec.sent, dtype=np.float64),
        done=np.asarray(rec.done, dtype=np.float64),
        ok=np.asarray(rec.ok, dtype=bool),
        offsets=offsets,
        status=np.asarray(cols[0], dtype=np.int64),
        limit=np.asarray(cols[1], dtype=np.int64),
        remaining=np.asarray(cols[2], dtype=np.int64),
        reset_time=np.asarray(cols[3], dtype=np.int64),
        item_error=np.asarray(cols[4], dtype=bool),
        first_error=np.asarray(first_error),
        rpc_error=np.asarray([r for r in rec.raw if not isinstance(r, bytes)],
                             dtype=str),
    )


def main() -> int:
    job_path, out_path = sys.argv[1], sys.argv[2]
    with open(job_path, "rb") as f:
        job = pickle.load(f)
    rec = asyncio.run(run(job))
    write_out(rec, out_path)
    print(f"DONE calls={len(rec.call)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
