"""/debug/profile while stop_trace outlasts the capture: the reply
starts at once and stays alive with newlines until the JSON follows
(a loaded server's stop runs past a client's idle timeout, PERF.md §6);
a capture that ends in time answers as before, status and all."""

import asyncio
import json
import time

from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from gubernator_tpu.service import gateway


def get_profile(monkeypatch, capture, heartbeat_s):
    monkeypatch.setattr(gateway, "_PROFILE_HEARTBEAT_S", heartbeat_s)
    monkeypatch.setattr(gateway._profiler, "capture", capture)

    async def go():
        app = web.Application()
        gateway.add_debug_routes(app, object())
        async with TestClient(TestServer(app)) as client:
            r = await client.get("/debug/profile?seconds=0.05")
            return r.status, await r.read()

    status, raw = asyncio.run(go())
    # the guard is back whatever became of the capture
    assert gateway._PROFILE_GUARD.acquire(blocking=False)
    gateway._PROFILE_GUARD.release()
    return status, raw


def test_long_stop_is_kept_alive_with_newlines(monkeypatch):
    def slow(seconds, python=False):
        time.sleep(0.6)  # the window, then a long stop_trace
        return {"trace_dir": "/somewhere", "seconds": seconds}

    status, raw = get_profile(monkeypatch, slow, 0.05)
    assert status == 200
    assert raw.startswith(b"\n") and raw.count(b"\n") >= 3
    # what the benchmark's harness does with the reply
    assert json.loads(raw) == {"trace_dir": "/somewhere", "seconds": 0.05}


def test_long_failing_capture_says_so_in_the_body(monkeypatch):
    def broken(seconds, python=False):
        time.sleep(0.3)
        raise RuntimeError("no profiler here")

    status, raw = get_profile(monkeypatch, broken, 0.05)
    assert status == 200  # the reply had begun
    assert "no profiler here" in json.loads(raw)["error"]


def test_capture_that_ends_in_time_answers_as_before(monkeypatch):
    status, raw = get_profile(
        monkeypatch, lambda seconds, python=False: {"files": 2}, 5.0)
    assert (status, json.loads(raw)) == (200, {"files": 2})
    assert not raw.startswith(b"\n")

    def broken(seconds, python=False):
        raise RuntimeError("no profiler here")

    status, raw = get_profile(monkeypatch, broken, 5.0)
    assert status == 503 and "no profiler here" in json.loads(raw)["error"]
