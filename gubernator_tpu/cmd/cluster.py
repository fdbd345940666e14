"""Local cluster binary: `python -m gubernator_tpu.cmd.cluster -n 4`
(reference cmd/gubernator-cluster/main.go — used by cross-language client
smoke tests, reference python/tests/test_client.py:25-37)."""

from __future__ import annotations

import argparse
import asyncio
import json
import signal
import sys


def main() -> None:
    p = argparse.ArgumentParser(description="in-process gubernator-tpu cluster")
    p.add_argument("-n", "--nodes", type=int, default=4)
    p.add_argument("--cache-size", type=int, default=8192)
    args = p.parse_args()

    from gubernator_tpu.utils.compilecache import enable_compile_cache

    enable_compile_cache()

    from gubernator_tpu.cluster import Cluster

    async def run() -> None:
        c = await Cluster.start(args.nodes, cache_size=args.cache_size)
        info = [
            {"grpc": d.grpc_address, "http": d.http_address} for d in c.daemons
        ]
        # One ready line on stdout for parent processes to parse.
        print("READY " + json.dumps(info), flush=True)
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        await c.stop()

    asyncio.run(run())


if __name__ == "__main__":
    sys.exit(main())
